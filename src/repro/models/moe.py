"""Mixture-of-Experts decoder (Mixtral / DBRX class).

Expert layer uses switch-style top-k routing with capacity-bounded token
dropping and scatter dispatch into a dense ``(E, C, d)`` buffer so the expert
matmuls stay MXU-shaped and the expert axis can be sharded over the mesh's
``model`` axis (expert parallelism — dispatch/undispatch become all-to-all
class collectives under GSPMD).

Aux losses (load-balance + router z-loss) are returned alongside the output
and surfaced by the train step.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as nn
from repro.sharding.context import constrain_batch, constrain_expert
from repro.models import transformer as tfm


# ---------------------------------------------------------------------------
# expert MLP bank + router
# ---------------------------------------------------------------------------

def init_moe_mlp(key, cfg):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    return {
        "router": nn.dense_init(ks[0], (d, E), d, cfg.param_dtype),
        "w_gate": nn.dense_init(ks[1], (E, d, f), d, cfg.param_dtype),
        "w_up": nn.dense_init(ks[2], (E, d, f), d, cfg.param_dtype),
        "w_down": nn.dense_init(ks[3], (E, f, d), f, cfg.param_dtype),
    }


def expert_capacity(cfg, n_tokens: int) -> int:
    cap = int(cfg.capacity_factor * n_tokens * cfg.top_k / cfg.n_experts)
    return max(8, ((cap + 7) // 8) * 8)   # pad to MXU-friendly multiple


MOE_SEQ_CHUNK = 1024


def _shardmap_applicable(cfg, batch_size: int):
    """Expert-parallel all_to_all path: usable when a mesh context is
    active, the expert count divides the model axis, and the batch divides
    the data axes (shard_map in_specs are hard constraints)."""
    from repro.sharding.context import _STATE
    from repro.sharding.specs import batch_axes
    mesh = _STATE.get("mesh")
    if mesh is None or "model" not in mesh.axis_names:
        return None
    if not _STATE.get("moe_shardmap", True):
        return None
    if cfg.n_experts % mesh.shape["model"] != 0:
        return None
    B = batch_axes(mesh)
    data_size = 1
    for a in (B if isinstance(B, tuple) else (B,)):
        data_size *= mesh.shape[a]
    if batch_size % data_size != 0:
        return None
    return mesh


def moe_mlp(params, x, cfg):
    """Dispatch entry point.

    * With an active mesh whose model axis divides the expert count:
      shard_map expert parallelism with explicit ``all_to_all`` — every
      buffer is member-local, sidestepping GSPMD's inability to shard
      scatter/gather batching dims (DESIGN.md §6b.4).
    * Otherwise: the GSPMD path, seq-chunked so the (device-replicated)
      dispatch buffers stay bounded.
    """
    b, s, d = x.shape
    # keep the dispatch buffers ~constant regardless of path: chunk so that
    # b x chunk stays near 16k tokens (buffers are device-replicated on the
    # GSPMD path and member-local but capacity-proportional on shard_map)
    chunk = min(MOE_SEQ_CHUNK, max(256, 16384 // max(b, 1)))
    if s <= chunk or s % chunk != 0:
        return _moe_dispatch(params, x, cfg)
    nch = s // chunk
    xs = x.reshape(b, nch, chunk, d).transpose(1, 0, 2, 3)

    def body(_, xc):
        y, aux = _moe_dispatch(params, xc, cfg)
        return None, (y, aux)

    _, (ys, auxs) = jax.lax.scan(body, None, xs)
    y = ys.transpose(1, 0, 2, 3).reshape(b, s, d)
    aux = jax.tree.map(lambda a: jnp.mean(a, axis=0), auxs)
    return y, aux


def _moe_dispatch(params, x, cfg):
    mesh = _shardmap_applicable(cfg, x.shape[0])
    if mesh is not None:
        return _moe_mlp_shardmap(params, x, cfg, mesh)
    return _moe_mlp_inner(params, x, cfg)


def _routing(x, router, cfg):
    """Top-k routing + positions-within-expert (group-local, slot-major)."""
    b, s, _ = x.shape
    E, K = cfg.n_experts, cfg.top_k
    C = expert_capacity(cfg, s)
    logits = (x @ router.astype(x.dtype)).astype(jnp.float32)   # (b,s,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)             # (b,s,K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)     # (b,s,K,E)
    slotmajor = onehot.transpose(0, 2, 1, 3).reshape(b, K * s, E)
    pos = jnp.cumsum(slotmajor, axis=1) - slotmajor
    pos = pos.reshape(b, K, s, E).transpose(0, 2, 1, 3)
    pos_in_expert = jnp.take_along_axis(
        pos, expert_idx[..., None], axis=-1)[..., 0]            # (b,s,K)
    keep = pos_in_expert < C
    density = jnp.mean(jax.nn.one_hot(expert_idx[..., 0], E,
                                      dtype=jnp.float32), axis=(0, 1))
    router_prob = jnp.mean(probs, axis=(0, 1))
    aux = {"lb_loss": E * jnp.sum(density * router_prob),
           "z_loss": jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
           "frac_dropped": 1.0 - jnp.mean(keep.astype(jnp.float32))}
    return gate_vals, expert_idx, pos_in_expert, keep, C, aux


def _moe_mlp_shardmap(params, x, cfg, mesh):
    """Expert parallelism with explicit all_to_all under jax.shard_map.

    Every model-axis member owns E/model experts.  Tokens are dispatched
    into member-local (b_loc, E, C, d) buffers, exchanged over the model
    axis (each member receives the slots destined for its experts from all
    peers), computed with the local expert weights, and exchanged back.
    All indexing is member-local — no cross-shard scatter/gather for GSPMD
    to replicate.
    """
    from jax.sharding import PartitionSpec as P
    from repro.sharding.specs import batch_axes

    b, s, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    M = mesh.shape["model"]
    e_per = E // M
    B = batch_axes(mesh)

    # routing + aux on the plain GSPMD path (cheap elementwise math)
    gate_vals, expert_idx, pos_in_expert, keep, C, aux = _routing(
        x, params["router"], cfg)
    dt = x.dtype

    def local(xb, gates_b, eidx_b, pos_b, keep_b, wg, wu, wd):
        bl, sl, _ = xb.shape
        # member-local dispatch buffer (bl, E, C, d)
        flat_e = jnp.where(keep_b, eidx_b, E)
        pos_c = jnp.where(keep_b, pos_b, 0)
        rows = jnp.broadcast_to(jnp.arange(bl)[:, None, None], (bl, sl, K))
        buf = jnp.zeros((bl, E + 1, C, d), dt)
        buf = buf.at[rows.reshape(bl, -1), flat_e.reshape(bl, -1),
                     pos_c.reshape(bl, -1)].set(
            jnp.repeat(xb[:, :, None], K, axis=2).reshape(bl, -1, d),
            mode="drop")
        buf = buf[:, :E]

        # exchange: dim0 = destination member (owner of the expert group)
        send = buf.reshape(bl, M, e_per, C, d).transpose(1, 0, 2, 3, 4)
        recv = jax.lax.all_to_all(send, "model", split_axis=0,
                                  concat_axis=0)   # (M_src, bl, e_per, C, d)

        # local expert compute (wg/wu: (e_per, d, f); wd: (e_per, f, d))
        g = jnp.einsum("mbjcd,jdf->mbjcf", recv, wg.astype(dt))
        u = jnp.einsum("mbjcd,jdf->mbjcf", recv, wu.astype(dt))
        yexp = jnp.einsum("mbjcf,jfd->mbjcd", jax.nn.silu(g) * u,
                          wd.astype(dt))

        # exchange back: dim0 returns to the source member
        back = jax.lax.all_to_all(yexp, "model", split_axis=0,
                                  concat_axis=0)   # (M, bl, e_per, C, d)
        yfull = back.transpose(1, 0, 2, 3, 4).reshape(bl, E, C, d)

        # member-local combine
        slot = flat_e.clip(0, E - 1) * C + pos_b.clip(0, C - 1)
        gathered = jax.vmap(lambda ye, ix: ye.reshape(E * C, d)[ix])(
            yfull, slot.reshape(bl, -1)).reshape(bl, sl, K, d)
        gathered = jnp.where(keep_b[..., None], gathered, 0)
        return jnp.sum(gathered * gates_b[..., None].astype(dt), axis=2)

    y = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(B, None, None), P(B, None, None), P(B, None, None),
                  P(B, None, None), P(B, None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=P(B, None, None),
        check_vma=False,
    )(x, gate_vals, expert_idx, pos_in_expert, keep,
      params["w_gate"], params["w_up"], params["w_down"])
    return y, aux


def _moe_mlp_inner(params, x, cfg):
    """x: (b, s, d) -> (y, aux) with aux = {"lb_loss", "z_loss", "frac_dropped"}.

    Group-local dispatch (GShard-style): each batch row is a routing group
    with its own capacity, so dispatch/combine indexing never crosses the
    batch (=data-axis) sharding — the expert dimension alone travels over
    the 'model' axis (expert parallelism, all-to-all class collectives).
    A single global capacity pool would need cross-data-shard gathers that
    GSPMD replicates (measured 210 GB/device on dbrx-132b prefill_32k).
    """
    b, s, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    C = expert_capacity(cfg, s)                    # capacity per group (row)

    logits = (x @ params["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                     # (b,s,E)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)             # (b,s,K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # position of each (token, slot) within its expert, slot-major within a
    # group so slot 0 wins capacity before slot 1 (standard switch ordering)
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)     # (b,s,K,E)
    slotmajor = onehot.transpose(0, 2, 1, 3).reshape(b, K * s, E)
    pos = jnp.cumsum(slotmajor, axis=1) - slotmajor             # (b,K*s,E)
    pos = pos.reshape(b, K, s, E).transpose(0, 2, 1, 3)         # (b,s,K,E)
    pos_in_expert = jnp.take_along_axis(
        pos, expert_idx[..., None], axis=-1)[..., 0]            # (b,s,K)
    keep = pos_in_expert < C
    frac_dropped = 1.0 - jnp.mean(keep.astype(jnp.float32))

    # scatter tokens into the (b, E, C, d) dispatch buffer (group-local)
    flat_e = jnp.where(keep, expert_idx, E)     # dropped -> out-of-range row
    pos_c = jnp.where(keep, pos_in_expert, 0)
    rows = jnp.broadcast_to(jnp.arange(b)[:, None, None], (b, s, K))
    buf = jnp.zeros((b, E + 1, C, d), x.dtype)
    buf = buf.at[rows.reshape(b, -1),
                 flat_e.reshape(b, -1),
                 pos_c.reshape(b, -1)].set(
        jnp.repeat(x[:, :, None], K, axis=2).reshape(b, -1, d), mode="drop")
    buf = buf[:, :E]                             # (b, E, C, d)
    buf = constrain_expert(buf)                  # b@data, E@model

    # expert compute (E stays a shardable axis; group dim stays on data)
    dt = x.dtype
    g = jnp.einsum("becd,edf->becf", buf, params["w_gate"].astype(dt))
    u = jnp.einsum("becd,edf->becf", buf, params["w_up"].astype(dt))
    h = constrain_expert(jax.nn.silu(g) * u)     # (b, E, C, f)
    yexp = jnp.einsum("becf,efd->becd", h, params["w_down"].astype(dt))
    yexp = constrain_expert(yexp)                # (b, E, C, d)

    # combine: group-local gather — vmap over the group dim so the lowered
    # gather carries an operand batching dim GSPMD can keep on 'data'
    # (flat advanced indexing lowers to a batchless gather that SPMD
    # replicates: 103 GB/device on dbrx prefill)
    slot = flat_e.clip(0, E - 1) * C + pos_in_expert.clip(0, C - 1)
    gathered = jax.vmap(lambda ye, ix: ye.reshape(E * C, d)[ix])(
        yexp, slot.reshape(b, -1))                             # (b,s*K,d)
    gathered = gathered.reshape(b, s, K, d)
    gathered = constrain_batch(gathered, seq_parallel=False)
    gathered = jnp.where(keep[..., None], gathered, 0)
    y = jnp.sum(gathered * gate_vals[..., None].astype(dt), axis=2)

    # aux losses (Switch Transformer eq. 4-6)
    density = jnp.mean(jax.nn.one_hot(expert_idx[..., 0], E,
                                      dtype=jnp.float32), axis=(0, 1))
    router_prob = jnp.mean(probs, axis=(0, 1))
    lb_loss = E * jnp.sum(density * router_prob)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux = {"lb_loss": lb_loss, "z_loss": z_loss, "frac_dropped": frac_dropped}
    return y, aux


# ---------------------------------------------------------------------------
# blocks / model
# ---------------------------------------------------------------------------

def init_layer(key, cfg):
    k1, k2 = jax.random.split(key)
    return {
        "attn_norm": nn.init_rmsnorm(cfg.d_model, cfg.param_dtype),
        "attn": nn.init_attention(k1, cfg),
        "mlp_norm": nn.init_rmsnorm(cfg.d_model, cfg.param_dtype),
        "moe": init_moe_mlp(k2, cfg),
    }


def init_params(cfg, key):
    ke, kl = jax.random.split(key)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    stacked = jax.vmap(lambda k: init_layer(k, cfg))(layer_keys)
    return {
        "embed": nn.init_embedding(ke, cfg.vocab_size, cfg.d_model,
                                   cfg.param_dtype),
        "layers": stacked,
        "final_norm": nn.init_rmsnorm(cfg.d_model, cfg.param_dtype),
    }


def apply_layer(cfg, lp, x, *, window=None):
    xn = constrain_batch(nn.rms_norm(lp["attn_norm"], x), seq_parallel=False)
    h, _ = nn.attention(lp["attn"], xn, cfg,
                        causal=cfg.causal,
                        window=window if window is not None else cfg.window,
                        impl=cfg.attn_impl)
    x = x + h
    xn = constrain_batch(nn.rms_norm(lp["mlp_norm"], x), seq_parallel=False)
    y, aux = moe_mlp(lp["moe"], xn, cfg)
    return x + y, aux


def apply_layer_range(cfg, stacked_slice, x, *, window=None, remat=None):
    remat = cfg.remat if remat is None else remat
    fn = partial(apply_layer, cfg, window=window)
    if remat:
        fn = jax.checkpoint(fn)

    def body(h, lp):
        h, aux = fn(lp, h)
        return constrain_batch(h), (aux["lb_loss"], aux["z_loss"])

    out, (lb, zl) = jax.lax.scan(body, x, stacked_slice)
    return out, {"lb_loss": jnp.mean(lb), "z_loss": jnp.mean(zl)}


def forward(cfg, params, batch, *, window=None, return_aux=False,
            last_only=False):
    x = tfm.embed_inputs(cfg, params, batch)
    x, aux = apply_layer_range(cfg, params["layers"], x, window=window)
    if last_only:
        x = x[:, -1:]
    x = nn.rms_norm(params["final_norm"], x)
    logits = nn.unembed(params["embed"], x)
    return (logits, aux) if return_aux else logits


def init_decode_state(cfg, batch: int, max_seq: int):
    return {"kv": nn.init_kv_cache(cfg, batch, max_seq)}


def decode_step(cfg, params, state, tokens, *, window=None):
    x = nn.embed(params["embed"], tokens, cfg.dtype)
    kv = state["kv"]

    def body(h, xs):
        lp, k_l, v_l = xs
        cache = {"k": k_l, "v": v_l, "index": kv["index"]}
        positions = cache["index"] + jnp.arange(h.shape[1])[None, :]
        positions = jnp.broadcast_to(positions, h.shape[:2])
        a, nc = nn.attention(lp["attn"], nn.rms_norm(lp["attn_norm"], h), cfg,
                             positions=positions, causal=True,
                             window=window if window is not None else cfg.window,
                             kv_cache=cache)
        h = h + a
        y, _ = moe_mlp(lp["moe"], nn.rms_norm(lp["mlp_norm"], h), cfg)
        return constrain_batch(h + y), (nc["k"], nc["v"])

    x, (nk, nv) = jax.lax.scan(body, x, (params["layers"], kv["k"], kv["v"]))
    x = nn.rms_norm(params["final_norm"], x)
    logits = nn.unembed(params["embed"], x)
    new_state = {"kv": {"k": nk, "v": nv,
                        "index": kv["index"] + tokens.shape[1]}}
    return logits, new_state


def _register():
    import sys

    from repro.models import registry
    registry.register(registry.FamilySpec(
        family="moe", module=sys.modules[__name__],
        batched_prefill=True, padded_prefill=False, paging=False,
        pure_kv_state=True, servable=True, token_stream_data=True,
        notes={
            "padded_prefill": "capacity-bounded expert routing couples "
                              "tokens: pad tokens consume expert capacity "
                              "and displace real tokens' routes",
            "paging": "expert capacity is a function of the token batch, "
                      "coupling decode lanes: a batched paged step would "
                      "not be token-identical to per-lane decode",
            "spec_draftable": "capacity-bounded routing couples the k "
                              "verified tokens: a multi-token verify would "
                              "route differently than token-by-token decode",
        }))


_register()
