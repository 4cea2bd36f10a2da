"""Plain reference of one chip's share of Moonlight-16B-A3B, its trainer,
and the counts its metrics need.

The model as the configuration file states it (``model``): token embedding
(untied), one dense block, then MoE blocks, a final RMSNorm and the output
head over the vocabulary slice, with next-token cross-entropy over every
position.  A block is pre-norm (RMSNorm, eps from the file):

- latent attention: q = x Wq, per head [nope 128, rope 64]; kv = x Wkv_a,
  c = RMSNorm(kv[:512]) (eps 1e-6), k_pe = kv[512:] (one rope key for all
  heads);
  [k_nope 128, v 128] = c Wkv_b per head; keys [k_nope, rope(k_pe)],
  queries [q_nope, rope(q_pe)] (rope pairs halves, theta 50,000), causal
  softmax scaled by 192^-1/2, computed 512 queries at a time against
  every key, the later ones masked; out Wo;
- dense block: SwiGLU of width d_ff;
- MoE block: router logits x R (64 outputs) in float32, sigmoid scores;
  experts chosen by top-6 of (score + selection bias), weighted by their
  un-biased scores normalised to sum 1 (+1e-20) times 2.446.  Each held
  expert e (SwiGLU of width 1,408) is computed on every token and added
  with that token's weight for e (zero where e was not chosen): the held
  experts' part of the sum, as the program computes it; plus the shared
  experts (one SwiGLU of width 2 x 1,408) on every token.

Weights are drawn from the seed as the configuration states, without
importing the program: keys (embed, dense, layers, head) split from the
seed; per block (attention, MLP); attention (wq, wkv_a, wkv_b, wo); a
SwiGLU (gate, up, down); an MoE MLP (router, experts, shared), routed
expert e from fold_in(experts key, e); normal with std 1/sqrt(fan_in),
0.02 for the embedding, unit norm scales, a zero selection bias.

The trainer (``train``) keeps weights and Adam moments on the host, brings
one block at a time to the chip, and keeps each block's input there for
the backward pass; everything in float32 at HIGHEST precision, or, for the
control, with every matrix product's operands rounded to float8.  Query
blocks, held experts and loss slices each run one program under a
``lax`` loop, and ``precompile`` compiles every program of a sound run
ahead of time: the TPU compiler takes 15-19 s over one program holding a
large float32 HIGHEST matrix product, so the trainer compiles few, and
can compile them while other work runs.
"""

from __future__ import annotations

import json
import math
import time
from functools import lru_cache, partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

import refops as R
from reftrain import _adam

ATTN_BLOCK = 512
KV_NORM_EPS = 1e-6     # HF builds the latent's RMSNorm with its default
HEAD_ROWS = 4096


# -- weights ----------------------------------------------------------------

def _dense(key, shape, fan_in):
    return R.normal(key, shape, 1 / math.sqrt(fan_in))


def _swiglu(key, d, f):
    ks = jax.random.split(key, 3)
    return {"w_gate": _dense(ks[0], (d, f), d),
            "w_up": _dense(ks[1], (d, f), d),
            "w_down": _dense(ks[2], (f, d), f)}


def n_held(m: dict) -> int:
    return m["n_experts_held"] or m["n_experts"]


def held(m: dict) -> range:
    return range(m["expert_offset"], m["expert_offset"] + n_held(m))


def init_block(m: dict, key, dense: bool) -> dict:
    d, nh, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    nope, rope, v = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"])
    k_attn, k_mlp = jax.random.split(key)
    ka = jax.random.split(k_attn, 4)
    out = {"attn_norm": {"scale": jnp.ones((d,))},
           "attn": {"wq": _dense(ka[0], (d, nh * (nope + rope)), d),
                    "wkv_a": _dense(ka[1], (d, r + rope), d),
                    "kv_norm": {"scale": jnp.ones((r,))},
                    "wkv_b": _dense(ka[2], (r, nh * (nope + v)), r),
                    "wo": _dense(ka[3], (nh * v, d), nh * v)},
           "mlp_norm": {"scale": jnp.ones((d,))}}
    if dense:
        out["mlp"] = _swiglu(k_mlp, d, m["d_ff"])
        return out
    k_router, k_exp, k_shared = jax.random.split(k_mlp, 3)
    experts = [_swiglu(jax.random.fold_in(k_exp, e), d, m["moe_d_ff"])
               for e in held(m)]
    out["moe"] = {"router": _dense(k_router, (d, m["n_experts"]), d),
                  "bias": jnp.zeros((m["n_experts"],)),
                  **{k: jnp.stack([e[k] for e in experts])
                     for k in ("w_gate", "w_up", "w_down")},
                  "shared": _swiglu(k_shared, d,
                                    m["n_shared_experts"] * m["moe_d_ff"])}
    return out


def keys(m: dict, seed: int):
    """(embed key, [(name, key, dense)] per block, head key)."""
    ke, kd, kl, kh = jax.random.split(jax.random.PRNGKey(seed), 4)
    n_dense = m["first_k_dense"]
    blocks = [(f"dense.{i}", k, True)
              for i, k in enumerate(jax.random.split(kd, n_dense))]
    blocks += [(f"layers.{i}", k, False) for i, k in enumerate(
        jax.random.split(kl, m["n_layers"] - n_dense))]
    return ke, blocks, kh


def init_embed(m: dict, key) -> dict:
    return {"table": R.normal(key, (m["vocab_size"], m["d_model"]), 0.02)}


def init_head(m: dict, key) -> dict:
    return {"final_norm": {"scale": jnp.ones((m["d_model"],))},
            "lm_head": _dense(key, (m["d_model"], m["vocab_size"]),
                              m["d_model"])}


# -- the model --------------------------------------------------------------

def rms_norm(x, scale, eps: float):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                        + eps) * scale


def swiglu(p, x, precision):
    g = R.mm(x, p["w_gate"], precision)
    u = R.mm(x, p["w_up"], precision)
    return R.mm(jax.nn.silu(g) * u, p["w_down"], precision)


def causal_attention(q, k, v, precision: str):
    """q, k: (b, t, h, dq); v: (b, t, h, dv).  ATTN_BLOCK queries at a time
    against every key, those after the query masked out; one block's
    program serves them all (``lax.map``), each block rematerialised in
    the backward pass."""
    b, t, h, dq = q.shape
    n = min(ATTN_BLOCK, t)

    @jax.checkpoint
    def block(xs):
        qb, lo = xs
        s = R.einsum("bqhd,bkhd->bhqk", qb, k, precision) / math.sqrt(dq)
        qpos = lo + jnp.arange(n)
        s = jnp.where(jnp.arange(t)[None, :] <= qpos[:, None], s, -jnp.inf)
        return R.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                        precision)
    qs = jnp.moveaxis(q.reshape(b, t // n, n, h, dq), 1, 0)
    o = jax.lax.map(block, (qs, jnp.arange(0, t, n)))
    return jnp.moveaxis(o, 0, 1).reshape(b, t, h, v.shape[-1])


def mla(m: dict, a: dict, x, precision: str):
    b, t, _ = x.shape
    nh, r = m["n_heads"], m["kv_lora_rank"]
    nope, rope = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    q = R.mm(x, a["wq"], precision).reshape(b, t, nh, nope + rope)
    kv = R.mm(x, a["wkv_a"], precision)
    c = rms_norm(kv[..., :r], a["kv_norm"]["scale"], KV_NORM_EPS)
    kvb = R.mm(c, a["wkv_b"], precision).reshape(b, t, nh, -1)
    pos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    k_pe = R.rope(kv[..., r:][:, :, None, :], pos, m["rope_theta"])
    q = jnp.concatenate([q[..., :nope],
                         R.rope(q[..., nope:], pos, m["rope_theta"])], -1)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_pe, (b, t, nh, rope))], -1)
    o = causal_attention(q, k, kvb[..., nope:], precision)
    return R.mm(o.reshape(b, t, -1), a["wo"], precision)


def moe(m: dict, p: dict, x, precision: str):
    """Shared experts plus the held experts' part of the routed sum."""
    xt = x.reshape(-1, x.shape[-1])
    scores = jax.nn.sigmoid(R.mm(xt, p["router"], precision))
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(p["bias"]),
                           m["top_k"])
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * m["routed_scaling_factor"]
    y = swiglu(p["shared"], xt, precision)

    @jax.checkpoint
    def held_part(pe, we):
        return we[:, None] * swiglu(pe, xt, precision)

    def expert(y, xs):
        # one held expert's program serves them all (``lax.scan``)
        e, pe = xs
        we = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        return y + held_part(pe, we), None
    y, _ = jax.lax.scan(expert, y, (jnp.asarray(list(held(m))), {
        k: p[k] for k in ("w_gate", "w_up", "w_down")}))
    return y.reshape(x.shape)


def block(m: dict, lp: dict, x, precision: str):
    """One block.  x: (b, t, d) float32."""
    eps = m["norm_eps"]
    x = x + mla(m, lp["attn"], rms_norm(x, lp["attn_norm"]["scale"], eps),
                precision)
    xn = rms_norm(x, lp["mlp_norm"]["scale"], eps)
    if "mlp" in lp:
        return x + swiglu(lp["mlp"], xn, precision)
    return x + moe(m, lp["moe"], xn, precision)


def head_loss(m: dict, hp: dict, x, labels, precision: str):
    """Mean cross-entropy over slices of HEAD_ROWS rows, each
    rematerialised, so the full (rows, vocab) logits never coexist; one
    slice's program serves them all (``lax.scan``)."""
    d = x.shape[-1]
    n = HEAD_ROWS if x.size // d % HEAD_ROWS == 0 else x.size // d
    xs = x.reshape(-1, n, d)
    ls = labels.reshape(-1, n)

    @jax.checkpoint
    def one(xc, lc):
        xn = rms_norm(xc, hp["final_norm"]["scale"], m["norm_eps"])
        return R.xent(R.mm(xn, hp["lm_head"], precision), lc)

    def body(total, xl):
        return total + one(*xl), None
    total, _ = jax.lax.scan(body, jnp.float32(0), (xs, ls))
    return total / xs.shape[0]


def forward_logits(m: dict, params: dict, tokens, precision: str = "f32"):
    """The whole share's logits (small sizes): params as ``init_params``."""
    x = params["embed"]["table"][tokens]
    for lp in params["blocks"]:
        x = block(m, lp, x, precision)
    hp = params["head"]
    xn = rms_norm(x, hp["final_norm"]["scale"], m["norm_eps"])
    return R.mm(xn, hp["lm_head"], precision)


def forward_loss(m: dict, params: dict, tokens, labels,
                 precision: str = "f32"):
    """The whole share's loss (small sizes): params as ``init_params``."""
    x = params["embed"]["table"][tokens]
    for lp in params["blocks"]:
        x = block(m, lp, x, precision)
    return head_loss(m, params["head"], x, labels, precision)


def init_params(m: dict, seed: int) -> dict:
    ke, blocks, kh = keys(m, seed)
    return {"embed": init_embed(m, ke), "head": init_head(m, kh),
            "blocks": [init_block(m, k, dense) for _, k, dense in blocks]}


# -- the trainer ------------------------------------------------------------

_host = jax.device_get          # every leaf's copy starts before any lands


class _Program:
    """A jitted program that can be compiled ahead of time for given
    argument shapes (``compile``, from any thread); a call with arguments
    of those shapes runs that executable, any other compiles as
    ``jax.jit`` does."""

    def __init__(self, fn, **jit_kw):
        self.jit = jax.jit(fn, **jit_kw)
        self.ready = {}

    def compile(self, *shapes) -> None:
        self.ready[_signature(shapes)] = self.jit.lower(*shapes).compile()

    def __call__(self, *args):
        return self.ready.get(_signature(args), self.jit)(*args)


def _signature(args) -> tuple:
    leaves, tree = jax.tree.flatten(args)
    return tree, tuple((tuple(a.shape), str(a.dtype)) for a in leaves)


_tree_norms = _Program(lambda tree: jax.tree.map(
    lambda a: jnp.sqrt(jnp.sum(jnp.square(a))), tree))


def leaf_norms(prefix: str, tree) -> dict:
    """``reftrain.leaf_norms``, in one program per tree."""
    flat = jax.tree_util.tree_flatten_with_path(_tree_norms(tree))[0]
    return {".".join([prefix] + [str(getattr(k, "key", k)) for k in path]):
            float(v) for path, v in flat}


@lru_cache(maxsize=None)
def _programs(m_json: str, opt_json: str, precision: str):
    """The trainer's programs, made once per (model, optimizer, precision)
    in a process: a control or a second seed compiles nothing the first
    did."""
    m, opt = json.loads(m_json), json.loads(opt_json)
    one = partial(block, m, precision=precision)

    def bwd(lp, x, g):
        return jax.vjp(one, lp, x)[1](g)
    return SimpleNamespace(
        fwd=_Program(one), bwd=_Program(bwd),
        head=_Program(jax.value_and_grad(
            partial(head_loss, m, precision=precision), argnums=(0, 1))),
        adam=_Program(partial(_adam, lr=opt["lr"], b1=opt["b1"],
                              b2=opt["b2"], eps=opt["eps"],
                              wd=opt["weight_decay"]),
                      donate_argnums=(0, 2, 3)),
        embed_grad=_Program(lambda g_table, tokens, g_x: g_table.at[
            tokens.reshape(-1)].add(g_x.reshape(-1, g_x.shape[-1]))),
        init_b={dense: _Program(partial(init_block, m, dense=dense))
                for dense in (True, False)},
        init_e=_Program(partial(init_embed, m)),
        init_h=_Program(partial(init_head, m)),
        diff=_Program(lambda a, b: jax.tree.map(jnp.subtract, a, b)))


def _json(d: dict) -> str:
    return json.dumps(d, sort_keys=True)


def precompile(m: dict, opt: dict, batch: int, seq: int) -> None:
    """Compile every program ``train`` runs in float32 on whole batches of
    (batch, seq), largest first, so that a caller can do it in another
    thread while other work runs.  Nothing compiled here is written to the
    persistent compilation cache."""
    from jax._src import config as jax_config
    P = _programs(_json(m), _json(opt), "f32")
    S = jax.ShapeDtypeStruct
    key = S((2,), jnp.uint32)
    x = S((batch, seq, m["d_model"]), jnp.float32)
    ids = S((batch, seq), jnp.int32)
    t = S((), jnp.float32)
    groups = {dense: jax.eval_shape(P.init_b[dense].jit, key)
              for dense in (False, True)}
    embed = jax.eval_shape(P.init_e.jit, key)
    head = jax.eval_shape(P.init_h.jit, key)
    with jax_config.persistent_cache_min_compile_time_secs(float("inf")):
        for dense in (False, True):
            P.bwd.compile(groups[dense], x, x)
            P.fwd.compile(groups[dense], x)
        P.head.compile(head, x, ids)
        P.embed_grad.compile(embed["table"], ids, x)
        for tree in (*groups.values(), embed, head):
            P.adam.compile(tree, tree, tree, tree, t)
            P.diff.compile(tree, tree)
            _tree_norms.compile(tree)
        for dense in (False, True):
            P.init_b[dense].compile(key)
        P.init_e.compile(key)
        P.init_h.compile(key)


def train(m: dict, opt: dict, seed: int, batch_fn, steps: int,
          precision: str = "f32", rows: int | None = None) -> dict:
    """Run ``steps`` AdamW steps from the seed; ``batch_fn(k)`` gives step
    k's ``{"tokens", "labels"}``.  ``rows`` keeps only the first rows of
    each batch (the half-batch control).  Returns ``losses``, ``grad_norm``
    (first step) and ``change_norm`` by leaf, as ``reftrain.train``, and
    ``step_s``, the seconds of each step and of the closing readings."""
    ke, blocks, kh = keys(m, seed)
    P = _programs(_json(m), _json(opt), precision)
    clock = [time.perf_counter()]

    # host: weights and both moments of every group
    params = {"embed": _host(P.init_e(ke)), "head": _host(P.init_h(kh))}
    for name, k, dense in blocks:
        params[name] = _host(P.init_b[dense](k))
    def zeros(tree):                # untouched pages until first read
        return jax.tree.map(lambda a: np.zeros(a.shape, a.dtype), tree)
    mom = {n: (zeros(p), zeros(p)) for n, p in params.items()}

    def step(name, g, t):
        p, (mo, ve) = params[name], mom[name]
        p, mo, ve = P.adam(jax.device_put(p), g, jax.device_put(mo),
                           jax.device_put(ve), t)
        params[name], mom[name] = _host((p, (mo, ve)))

    losses, grad_norm, step_s = [], {}, []
    for k in range(steps):
        batch = batch_fn(k)
        tokens = jnp.asarray(batch["tokens"][:rows])
        labels = jnp.asarray(batch["labels"][:rows])
        t = jnp.float32(k + 1)
        xs = [jnp.asarray(params["embed"]["table"])[tokens]]
        for name, _, _ in blocks:
            xs.append(P.fwd(jax.device_put(params[name]), xs[-1]))
        loss, (g_head, g_x) = P.head(jax.device_put(params["head"]),
                                     xs.pop(), labels)
        losses.append(float(loss))
        grads = {"head": g_head}
        for name, _, _ in reversed(blocks):
            grads[name], g_x = P.bwd(jax.device_put(params[name]), xs.pop(),
                                     g_x)
            if k == 0:
                grad_norm.update(leaf_norms(name, grads[name]))
            step(name, grads.pop(name), t)
        grads["embed"] = {"table": P.embed_grad(
            jnp.zeros(params["embed"]["table"].shape), tokens, g_x)}
        del g_x
        for name in ("head", "embed"):
            if k == 0:
                grad_norm.update(leaf_norms(name, grads[name]))
            step(name, grads.pop(name), t)
        clock.append(time.perf_counter())
    del mom
    change = {}
    for name, k, dense in blocks:
        change.update(leaf_norms(name, P.diff(params[name],
                                              P.init_b[dense](k))))
    change.update(leaf_norms("embed", P.diff(params["embed"], P.init_e(ke))))
    change.update(leaf_norms("head", P.diff(params["head"], P.init_h(kh))))
    clock.append(time.perf_counter())
    return {"losses": losses, "grad_norm": grad_norm, "change_norm": change,
            "step_s": [round(b - a, 3) for a, b in zip(clock, clock[1:])]}


# -- counts -----------------------------------------------------------------

def _mla_params(m: dict) -> int:
    d, nh, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    nope, rope, v = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"])
    return (d * nh * (nope + rope) + d * (r + rope) + r
            + r * nh * (nope + v) + nh * v * d)


def n_params(m: dict, active: bool = False) -> float:
    """Parameters of the share; ``active``: those one token uses here, the
    routed experts counted at top_k x held / n_experts of one expert, the
    embedding table left out (a lookup, no product)."""
    d, f, E = m["d_model"], m["moe_d_ff"], m["n_experts"]
    expert = 3 * d * f
    routed = (m["top_k"] * n_held(m) / E if active else n_held(m)) * expert
    base = _mla_params(m) + 2 * d
    dense = base + 3 * d * m["d_ff"]
    moe = (base + routed + m["n_shared_experts"] * expert + d * E
           + (0 if active else E))
    n_dense = m["first_k_dense"]
    embed = 0 if active else m["vocab_size"] * d
    return (n_dense * dense + (m["n_layers"] - n_dense) * moe
            + embed + m["vocab_size"] * d + d)


def train_flops_per_token(m: dict, seq: int) -> float:
    """Model FLOPs of one trained token: 6 x the active parameters
    (``n_params(active=True)``: routed experts at 6 x 8/64 of one expert,
    the untied head counted, the embedding lookup not), plus causal latent
    attention, forward and backward: 3 x 2 x heads x (qk 192 + v 128) x
    (seq + 1) / 2 per layer (the mean key count of a causal row).
    Recomputation is not counted."""
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    attn = 3.0 * m["n_heads"] * (qk + m["v_head_dim"]) * (seq + 1)
    return 6.0 * n_params(m, active=True) + m["n_layers"] * attn


def routed_mm_cost(m: dict, rows_per_call: float, calls: int) -> tuple:
    """FLOPs and HBM bytes of ``calls`` grouped matmuls of the held experts
    (forward gate, up or down, or a backward product), each over
    ``rows_per_call`` rows in bf16: 2 x rows x d x f FLOPs; bytes of the
    row block in and out and of the held experts' matrices."""
    d, f, h = m["d_model"], m["moe_d_ff"], n_held(m)
    flops = 2.0 * rows_per_call * d * f * calls
    nbytes = 2.0 * (rows_per_call * (d + f) + h * d * f) * calls
    return flops, nbytes
