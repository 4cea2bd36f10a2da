"""The plain reference of a training job: the first steps of full-batch
AdamW on a reference model, computed layer by layer so that it fits one
chip, in float32 at HIGHEST precision (or in the control's float8).

A reference model module (``configs/<config>.py``) provides
``layer_keys``, ``init_layer``, ``init_embed``, ``init_final_norm``,
``layer`` and ``head_loss``.  The reference keeps weights and both Adam
moments on the device, the input of each layer for the backward pass, and
regenerates the initial weights from the seed at the end to measure how
far each leaf moved.

Readings, all by leaf (``embed.table``, ``final_norm.scale``,
``layers.<i>.attn.wq``, ...):
- ``losses``: the loss of each step;
- ``grad_norm``: the norm of the first step's gradient;
- ``change_norm``: the norm of (weights after the last step - initial).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


HEAD_CHUNKS = 8


def leaf_norms(prefix: str, tree) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = ".".join([prefix] + [str(getattr(k, "key", k)) for k in path])
        out[name] = float(jnp.sqrt(jnp.sum(jnp.square(
            leaf.astype(jnp.float32)))))
    return out


def _adam(p, g, m, v, t, *, lr, b1, b2, eps, wd):
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    p = jax.tree.map(lambda p_, m_, v_: p_ - lr * (
        (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps) + wd * p_), p, m, v)
    return p, m, v


def train(model, m: dict, opt: dict, seed: int, batch_fn, steps: int,
          precision: str = "f32", rows: int | None = None) -> dict:
    """Run ``steps`` AdamW steps from the seed; ``batch_fn(k)`` gives step
    k's ``{"tokens", "labels"}`` (k from 0).  ``rows`` keeps only the first
    rows of each batch, the mean taken over them: the fault of a step that
    leaves part of its batch out, for reading the check against it."""
    k_embed, lkeys = model.layer_keys(m, seed)
    init_layer = jax.jit(partial(model.init_layer, m))
    layer = partial(model.layer, m, precision=precision)
    fwd = jax.jit(layer)

    @jax.jit
    def bwd(lp, x, g):
        _, vjp = jax.vjp(layer, lp, x)
        return vjp(g)

    def chunked_loss(fn, table, x, labels):
        # the mean over HEAD_CHUNKS equal slices of the batch's rows, each
        # rematerialised, so the full (rows, vocab) logits never coexist
        xs = x.reshape(HEAD_CHUNKS, 1, -1, x.shape[-1])
        ls = labels.reshape(HEAD_CHUNKS, 1, -1)
        one = jax.checkpoint(partial(model.head_loss, m, fn, table,
                                     precision=precision))

        def body(total, xl):
            return total + one(*xl), None
        total, _ = jax.lax.scan(body, jnp.float32(0), (xs, ls))
        return total / HEAD_CHUNKS

    @jax.jit
    def head(fn, table, x, labels):
        return jax.value_and_grad(chunked_loss, argnums=(0, 1, 2))(
            fn, table, x, labels)

    @jax.jit
    def embed_grad(g_table, tokens, g_x):
        return g_table.at[tokens.reshape(-1)].add(
            g_x.reshape(-1, g_x.shape[-1]))

    adam = jax.jit(partial(_adam, lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                           eps=opt["eps"], wd=opt["weight_decay"]),
                   donate_argnums=(0, 2, 3))

    table = jax.jit(partial(model.init_embed, m))(k_embed)
    fnorm = model.init_final_norm(m)
    layers = [init_layer(k) for k in lkeys]
    zeros = partial(jax.tree.map, jnp.zeros_like)
    mom = {"table": (zeros(table), zeros(table)),
           "final_norm": (zeros(fnorm), zeros(fnorm)),
           "layers": [(zeros(lp), zeros(lp)) for lp in layers]}
    losses, grad_norm = [], {}
    for k in range(steps):
        batch = batch_fn(k)
        tokens = jnp.asarray(batch["tokens"][:rows])
        labels = jnp.asarray(batch["labels"][:rows])
        t = jnp.float32(k + 1)
        xs = [table[tokens]]
        for lp in layers:
            xs.append(fwd(lp, xs[-1]))
        loss, (g_fn, g_table, g_x) = head(fnorm, table, xs.pop(), labels)
        losses.append(float(loss))
        for i in reversed(range(len(layers))):
            g_lp, g_x = bwd(layers[i], xs.pop(), g_x)
            if k == 0:
                grad_norm.update(leaf_norms(f"layers.{i}", g_lp))
            mo, ve = mom["layers"][i]
            layers[i], mo, ve = adam(layers[i], g_lp, mo, ve, t)
            mom["layers"][i] = (mo, ve)
        g_table = embed_grad(g_table, tokens, g_x)
        if k == 0:
            grad_norm.update(leaf_norms("final_norm", g_fn))
            grad_norm.update(leaf_norms("embed", {"table": g_table}))
        mo, ve = mom["final_norm"]
        fnorm, mo, ve = adam(fnorm, g_fn, mo, ve, t)
        mom["final_norm"] = (mo, ve)
        mo, ve = mom["table"]
        table, mo, ve = adam(table, g_table, mo, ve, t)
        mom["table"] = (mo, ve)
        del g_table, g_fn, g_x
    del mom
    change = {}
    diff = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))
    for i, key in enumerate(lkeys):
        change.update(leaf_norms(f"layers.{i}",
                                 diff(layers[i], init_layer(key))))
    change.update(leaf_norms("final_norm",
                             diff(fnorm, model.init_final_norm(m))))
    change.update(leaf_norms("embed", {"table": diff(
        table, jax.jit(partial(model.init_embed, m))(k_embed))}))
    return {"losses": losses, "grad_norm": grad_norm, "change_norm": change}


def compare(prog: dict, ref: dict, grad_floor: float = 1e-3) -> dict:
    """The numbers a training check holds to its limits.

    - ``loss_gap``: the largest |program - reference| step loss;
    - ``grad_gap``: over leaves, the largest |program norm - reference
      norm| of the first gradient, over the larger of the reference
      leaf's norm and the median leaf's;
    - ``change_gap``: the same for the weights' change over the steps,
      over leaves whose reference gradient is at least ``grad_floor``
      times the median leaf's (a leaf with no gradient moves by
      round-off under Adam and is left out by this rule, never by name).
    """
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"],
                                              strict=True))

    def worst(key, names):
        med = float(np.median([ref[key][n] for n in names]))
        return max((abs(prog[key][n] - ref[key][n])
                    / max(ref[key][n], med), n) for n in names)

    names = sorted(ref["grad_norm"])
    if sorted(prog["grad_norm"]) != names or \
            sorted(prog["change_norm"]) != sorted(ref["change_norm"]):
        raise ValueError("program and reference leaves differ")
    g_med = float(np.median([ref["grad_norm"][n] for n in names]))
    moved = [n for n in names if ref["grad_norm"][n] >= grad_floor * g_med]
    grad_gap, grad_leaf = worst("grad_norm", names)
    change_gap, change_leaf = worst("change_norm", moved)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "grad_leaf": grad_leaf, "change_gap": change_gap,
            "change_leaf": change_leaf, "leaves": len(names),
            "leaves_moved": len(moved)}
