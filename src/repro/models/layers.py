"""Core neural-net layers shared by every architecture in the zoo.

Pure-functional style: every layer is an ``init_*`` returning a param pytree
(plain dicts of jnp arrays) plus an ``apply``-style function taking
``(params, inputs, cfg)``.  No framework (flax/haiku) — keeps the param tree
transparent for Hydra's shard-granular spilling and for pjit sharding rules.

Conventions
-----------
* ``cfg`` is a ``repro.configs.base.ArchConfig``.
* Stacked-layer params: callers stack per-layer trees along axis 0 and drive
  them with ``jax.lax.scan`` so the lowered HLO is O(1) in depth.
* Compute dtype is ``cfg.dtype`` (bf16 on TPU); params kept in
  ``cfg.param_dtype`` (f32 master copies).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

Params = dict  # nested dict[str, jnp.ndarray]


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis_size=None, dtype=jnp.float32):
    """Scaled-normal init (truncated-normal-free; fine for repro purposes)."""
    fan_in = in_axis_size if in_axis_size is not None else shape[0]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape) * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rms_norm(params: Params, x: jnp.ndarray, eps: float = 1e-6,
             use_kernel: bool = False) -> jnp.ndarray:
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.rms_norm(x, params["scale"], eps=eps)
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dtype)


def init_layernorm(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layer_norm(params: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)
            + params["bias"].astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 10000.0) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)                     # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]                        # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, optional qkv-bias / qk-norm / sliding window / cross-attn)
# ---------------------------------------------------------------------------

def init_attention(key, cfg) -> Params:
    d, nh, nkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": dense_init(ks[0], (d, nh * hd), d, cfg.param_dtype),
        "wk": dense_init(ks[1], (d, nkv * hd), d, cfg.param_dtype),
        "wv": dense_init(ks[2], (d, nkv * hd), d, cfg.param_dtype),
        "wo": dense_init(ks[3], (nh * hd, d), nh * hd, cfg.param_dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nh * hd,), cfg.param_dtype)
        p["bk"] = jnp.zeros((nkv * hd,), cfg.param_dtype)
        p["bv"] = jnp.zeros((nkv * hd,), cfg.param_dtype)
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd, cfg.param_dtype)
        p["k_norm"] = init_rmsnorm(hd, cfg.param_dtype)
    return p


def _project_qkv(params: Params, x: jnp.ndarray, xkv: jnp.ndarray, cfg):
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = x.dtype
    q = x @ params["wq"].astype(dt)
    k = xkv @ params["wk"].astype(dt)
    v = xkv @ params["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    q = q.reshape(*x.shape[:-1], nh, hd)
    k = k.reshape(*xkv.shape[:-1], nkv, hd)
    v = v.reshape(*xkv.shape[:-1], nkv, hd)
    if cfg.qk_norm:
        q = rms_norm(params["q_norm"], q)
        k = rms_norm(params["k_norm"], k)
    return q, k, v


# dense-path limit: above this many score elements (b·h·sq·skv) the scores
# are never held whole; attention runs blockwise, forward and backward
_SDPA_DENSE_ELEMS = 2 ** 28
_SDPA_BLOCK = 512
_NEG = -1e30


def _sdpa_dense(q, k, v, scale, qpos, kpos, causal, window):
    """q: (b, sq, nkv, g, hd) grouped; k: (b, skv, nkv, hd); v: (b, skv,
    nkv, hv)."""
    logits = jnp.einsum("bqkgh,bskh->bkgqs", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    logits = jnp.where(mask[None, None, None], logits, _NEG)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskv->bqkgv", probs, v.astype(jnp.float32))
    return out


def _block_mask(qp, kp, kok, causal, window):
    """(Bq, Bk) mask of one block pair; ``kok`` marks real (unpadded) keys."""
    mask = jnp.broadcast_to(kok[None, :], (qp.shape[0], kp.shape[0]))
    if causal:
        mask &= kp[None, :] <= qp[:, None]
    if window is not None:
        mask &= kp[None, :] > qp[:, None] - window
    return mask


def _block_needed(qp, kp, causal, window):
    """False when the mask rules out every pair of the block: the block is
    skipped, not computed."""
    need = jnp.bool_(True)
    if causal:
        need &= jnp.min(kp) <= jnp.max(qp)
    if window is not None:
        need &= jnp.max(kp) > jnp.min(qp) - window
    return need


def _blocks(x, n):
    """(b, n * B, ...) -> (n, b, B, ...)."""
    x = x.reshape(x.shape[0], n, -1, *x.shape[2:])
    return jnp.moveaxis(x, 1, 0)


def _scores(qi, kj, scale, mask):
    s = jnp.einsum("bqkgh,bskh->bkgqs", qi.astype(jnp.float32),
                   kj.astype(jnp.float32)) * scale
    return jnp.where(mask[None, None, None], s, _NEG)


@partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _flash(q, k, v, qpos, kpos, kok, scale, causal, window):
    return _flash_fwd(q, k, v, qpos, kpos, kok, scale, causal, window)[0]


def _flash_fwd(q, k, v, qpos, kpos, kok, scale, causal, window):
    """Online softmax over key blocks, one query block at a time.  Inputs
    are in blocks: q (nq, b, Bq, nkv, g, hd), k/v (nk, b, Bk, nkv, .),
    positions (n, B).  Returns out (nq, b, nkv, g, Bq, hv) f32."""
    nk = k.shape[0]
    _, b, bq, nkv, g, _ = q.shape
    hv = v.shape[-1]

    def q_body(_, xs):
        qi, qp = xs

        def k_body(j, carry):
            def compute(carry):
                m, l, acc = carry
                s = _scores(qi, k[j], scale,
                            _block_mask(qp, kpos[j], kok[j], causal, window))
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m - m_new)
                acc = acc * corr[..., None] + jnp.einsum(
                    "bkgqs,bskv->bkgqv", p, v[j].astype(jnp.float32))
                return m_new, l * corr + jnp.sum(p, axis=-1), acc
            return jax.lax.cond(_block_needed(qp, kpos[j], causal, window),
                                compute, lambda c: c, carry)

        m0 = jnp.full((b, nkv, g, bq), _NEG, jnp.float32)
        carry = (m0, jnp.zeros_like(m0),
                 jnp.zeros((b, nkv, g, bq, hv), jnp.float32))
        m, l, acc = jax.lax.fori_loop(0, nk, k_body, carry)
        return None, (acc / l[..., None], m + jnp.log(l))

    _, (out, lse) = jax.lax.scan(q_body, None, (q, qpos))
    return out, (q, k, v, qpos, kpos, kok, out, lse)


def _flash_bwd(scale, causal, window, res, dout):
    """Recompute each block's probabilities from the saved log-sum-exp; one
    pass over key blocks gives dk, dv, and dq accumulates in place."""
    q, k, v, qpos, kpos, kok, out, lse = res
    nq = q.shape[0]
    delta = jnp.sum(dout * out, axis=-1)               # (nq, b, nkv, g, Bq)

    def k_body(dq, xs):
        kj, vj, kp, ko = xs
        kf, vf = kj.astype(jnp.float32), vj.astype(jnp.float32)

        def q_body(i, carry):
            def compute(carry):
                dq, dk, dv = carry
                qi = q[i]
                s = _scores(qi, kj, scale,
                            _block_mask(qpos[i], kp, ko, causal, window))
                p = jnp.exp(s - lse[i][..., None])
                dv = dv + jnp.einsum("bkgqs,bkgqv->bskv", p, dout[i])
                dp = jnp.einsum("bkgqv,bskv->bkgqs", dout[i], vf)
                ds = p * (dp - delta[i][..., None]) * scale
                dq = dq.at[i].add(jnp.einsum("bkgqs,bskh->bqkgh", ds, kf))
                dk = dk + jnp.einsum("bkgqs,bqkgh->bskh", ds,
                                     qi.astype(jnp.float32))
                return dq, dk, dv
            return jax.lax.cond(_block_needed(qpos[i], kp, causal, window),
                                compute, lambda c: c, carry)

        dq, dk, dv = jax.lax.fori_loop(
            0, nq, q_body, (dq, jnp.zeros(kj.shape, jnp.float32),
                            jnp.zeros(vj.shape, jnp.float32)))
        return dq, (dk.astype(kj.dtype), dv.astype(vj.dtype))

    dq, (dk, dv) = jax.lax.scan(k_body, jnp.zeros(q.shape, jnp.float32),
                                (k, v, kpos, kok))
    return dq.astype(q.dtype), dk, dv, None, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def blockwise_attention(q, k, v, scale, qpos, kpos, causal, window,
                        block: int = _SDPA_BLOCK):
    """Attention whose scores live one (block x block) tile at a time in the
    forward and the backward pass (flash-style, in XLA).  Same arguments and
    result as ``_sdpa_dense``; sequences are padded to whole blocks."""
    b, sq = q.shape[:2]
    skv = k.shape[1]
    nq, nk = -(-sq // block), -(-skv // block)
    pq, pk = nq * block - sq, nk * block - skv

    def pad(x, n):
        return jnp.pad(x, [(0, 0), (0, n)] + [(0, 0)] * (x.ndim - 2))
    qpos = jnp.pad(qpos, (0, pq), mode="edge")
    kok = jnp.arange(nk * block) < skv
    kpos = jnp.pad(kpos, (0, pk), mode="edge")
    out = _flash(_blocks(pad(q, pq), nq), _blocks(pad(k, pk), nk),
                 _blocks(pad(v, pk), nk), qpos.reshape(nq, block),
                 kpos.reshape(nk, block), kok.reshape(nk, block),
                 scale, causal, window)
    # (nq, b, nkv, g, Bq, hv) -> (b, sq, nkv, g, hv)
    out = jnp.transpose(out, (1, 0, 4, 2, 3, 5))
    return out.reshape(b, nq * block, *out.shape[3:])[:, :sq]


def sdpa(q, k, v, *, causal: bool, window: Optional[int] = None,
         q_positions: Optional[jnp.ndarray] = None,
         kv_positions: Optional[jnp.ndarray] = None,
         impl: str = "xla") -> jnp.ndarray:
    """Scaled dot-product attention with GQA broadcast.

    q: (b, sq, nh, hd); k: (b, skv, nkv, hd); v: (b, skv, nkv, hv), whose
    head size may differ from q's.  nh % nkv == 0.  ``window``:
    sliding-window size (None = full).  Positions default to arange;
    decode passes explicit positions.  Scores in f32, scaled by hd^-1/2.
    """
    if impl in ("pallas", "pallas_interpret") and causal and q.shape[1] > 1:
        from repro.kernels import ops as kops
        return kops.flash_attention(
            q, k, v, causal=True, window=window,
            interpret=(impl == "pallas_interpret"))
    b, sq, nh, hd = q.shape
    skv, nkv, hv = k.shape[1], k.shape[2], v.shape[-1]
    groups = nh // nkv
    scale = 1.0 / math.sqrt(hd)
    qpos = (q_positions if q_positions is not None
            else jnp.arange(sq))
    kpos = (kv_positions if kv_positions is not None
            else jnp.arange(skv))

    qg = q.reshape(b, sq, nkv, groups, hd)

    from repro.sharding.context import constrain_q_seq
    qg = constrain_q_seq(qg.reshape(b, sq, nh, hd)).reshape(
        b, sq, nkv, groups, hd)

    if b * nh * sq * skv <= _SDPA_DENSE_ELEMS:
        out = _sdpa_dense(qg, k, v, scale, qpos, kpos, causal, window)
    else:
        out = blockwise_attention(qg, k, v, scale, qpos, kpos, causal,
                                  window)
    return out.reshape(b, sq, nh, hv).astype(q.dtype)


def attention(params: Params, x: jnp.ndarray, cfg, *,
              positions: Optional[jnp.ndarray] = None,
              causal: bool = True,
              window: Optional[int] = None,
              xkv: Optional[jnp.ndarray] = None,
              rope: bool = True,
              kv_cache: Optional[dict] = None,
              impl: str = "xla"):
    """Full attention layer.  Returns (out, new_kv_cache).

    kv_cache: {"k": (b, max_s, nkv, hd), "v": ..., "index": scalar} — decode
    appends at ``index`` and attends to the filled prefix.
    """
    b, sq, _ = x.shape
    cross = xkv is not None
    src = xkv if cross else x
    q, k, v = _project_qkv(params, x, src, cfg)
    if positions is None:
        positions = jnp.arange(sq)[None, :].astype(jnp.int32)
        positions = jnp.broadcast_to(positions, (b, sq))
    if rope and not cross:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif rope and cross:
        q = apply_rope(q, positions, cfg.rope_theta)

    new_cache = None
    if kv_cache is not None and not cross:
        idx = kv_cache["index"]
        ck = jax.lax.dynamic_update_slice_in_dim(
            kv_cache["k"], k.astype(kv_cache["k"].dtype), idx, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            kv_cache["v"], v.astype(kv_cache["v"].dtype), idx, axis=1)
        new_cache = {"k": ck, "v": cv, "index": idx + sq}
        skv = ck.shape[1]
        kvpos = jnp.arange(skv)
        qpos = idx + jnp.arange(sq)
        # mask out unwritten slots via the causal predicate (kvpos <= qpos)
        out = sdpa(q, ck, cv, causal=True, window=window,
                   q_positions=qpos, kv_positions=kvpos, impl="xla")
    elif kv_cache is not None and cross:
        # cross-attn cache holds precomputed encoder k/v
        out = sdpa(q, kv_cache["k"], kv_cache["v"], causal=False, impl="xla")
        new_cache = kv_cache
    else:
        out = sdpa(q, k, v, causal=causal, window=window, impl=impl)

    dt = x.dtype
    out = out.reshape(b, sq, cfg.n_heads * cfg.head_dim)
    return out @ params["wo"].astype(dt), new_cache


def _scatter_kv_rows(pages: dict, blk, off, k, v) -> dict:
    """Write K/V rows through the block table into a pages pytree.

    pages: {"k","v"} of (P, bs, nkv, hd) — plus {"k_scale","v_scale"} of
    (P, bs, nkv) when the pool is int8-quantized, in which case the rows
    are quantized per-row on write (`ref.quantize_kv`) and the scales land
    at the same table-addressed slots.  blk/off index rows; k/v are the
    new rows broadcast-compatible with pages[blk, off]."""
    from repro.kernels import ref as kref
    if "k_scale" in pages:
        kq, ksc = kref.quantize_kv(k)
        vq, vsc = kref.quantize_kv(v)
        return {"k": pages["k"].at[blk, off].set(kq),
                "v": pages["v"].at[blk, off].set(vq),
                "k_scale": pages["k_scale"].at[blk, off].set(ksc),
                "v_scale": pages["v_scale"].at[blk, off].set(vsc)}
    return {"k": pages["k"].at[blk, off].set(k.astype(pages["k"].dtype)),
            "v": pages["v"].at[blk, off].set(v.astype(pages["v"].dtype))}


def paged_attention_decode(params: Params, x: jnp.ndarray, cfg, *,
                           pages: dict,
                           tables: jnp.ndarray, lengths: jnp.ndarray,
                           window: Optional[int] = None,
                           impl: str = "jnp"):
    """One-token attention block over a paged KV cache (one layer's pages).

    x: (n, 1, d) *normed* hidden states, one decode lane per row.
    pages: {"k","v"} of (P, bs, nkv, hd) physical blocks (+ per-row
    {"k_scale","v_scale"} when int8-quantized); tables: (n, B) block ids
    (unused entries must name a valid block — the pool's garbage block);
    lengths: (n,) rows already written, i.e. this token's row index.

    Writes this step's K/V row through the block table (one scatter across
    lanes — inactive lanes all land in the shared garbage block) and
    attends to the ``[0, lengths]`` logical prefix via
    ``kernels.ops.paged_attention`` (the dequantizing
    ``paged_attention_quant`` for int8 pools).  Returns (out, pages).
    """
    n = x.shape[0]
    q, k, v = _project_qkv(params, x, x, cfg)
    positions = lengths[:, None]                       # (n, 1)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    bs = pages["k"].shape[1]
    blk = tables[jnp.arange(n), lengths // bs]
    off = lengths % bs
    pages = _scatter_kv_rows(pages, blk, off, k[:, 0], v[:, 0])
    from repro.kernels import ops as kops
    # the fused-layer impl falls back to plain paged attention here (the
    # quantized / non-SwiGLU configs the fused kernel doesn't cover)
    attn_impl = {"fused": "pallas",
                 "fused_interpret": "pallas_interpret"}.get(impl, impl)
    if "k_scale" in pages:
        out = kops.paged_attention_quant(
            q[:, 0], pages["k"], pages["v"], pages["k_scale"],
            pages["v_scale"], tables, lengths + 1, window=window,
            impl=attn_impl)
    else:
        out = kops.paged_attention(q[:, 0], pages["k"], pages["v"], tables,
                                   lengths + 1, window=window,
                                   impl=attn_impl)
    out = out.reshape(n, 1, cfg.n_heads * cfg.head_dim)
    return out @ params["wo"].astype(x.dtype), pages


def paged_decode_layer_fused(lp: Params, h: jnp.ndarray, cfg, *,
                             pages: dict,
                             tables: jnp.ndarray, lengths: jnp.ndarray,
                             window: Optional[int] = None,
                             interpret: bool = False):
    """One FULL pre-norm decode block through the fused Pallas kernel:
    attn-norm + QKV projection + rope + KV scatter run here (they write
    the pages); attention through the block table, wo projection,
    residual, MLP RMSNorm, SwiGLU, and the second residual all run inside
    one `kernels.fused_decode` launch.  Requires ``cfg.norm == 'rms'`` and
    ``cfg.mlp == 'swiglu'`` and an fp (non-quantized) pool — callers gate
    on that and fall back to the unfused path otherwise.

    h: (n, 1, d) raw residual stream.  Returns (new_h, pages).
    """
    n = h.shape[0]
    x = rms_norm(lp["attn_norm"], h)
    q, k, v = _project_qkv(lp["attn"], x, x, cfg)
    positions = lengths[:, None]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    bs = pages["k"].shape[1]
    blk = tables[jnp.arange(n), lengths // bs]
    off = lengths % bs
    pages = _scatter_kv_rows(pages, blk, off, k[:, 0], v[:, 0])
    from repro.kernels import ops as kops
    dt = h.dtype
    out = kops.fused_decode_layer(
        h[:, 0], q[:, 0], pages["k"], pages["v"], tables, lengths + 1,
        lp["attn"]["wo"].astype(dt), lp["mlp_norm"]["scale"].astype(dt),
        lp["mlp"]["w_gate"].astype(dt), lp["mlp"]["w_up"].astype(dt),
        lp["mlp"]["w_down"].astype(dt), window=window,
        impl="pallas_interpret" if interpret else "pallas")
    return out[:, None, :], pages


def paged_attention_verify(params: Params, x: jnp.ndarray, cfg, *,
                           pages: dict,
                           tables: jnp.ndarray, lengths: jnp.ndarray,
                           window: Optional[int] = None,
                           impl: str = "jnp"):
    """k-token attention block over a paged KV cache (speculative verify).

    The multi-token twin of ``paged_attention_decode``: x is ``(n, k, d)``
    *normed* hidden states — the last committed token followed by k-1 draft
    tokens per lane.  Writes all k K/V rows through the block table in one
    scatter (rows ``lengths + [0, k)``; lanes whose table names only the
    garbage block park their rows there harmlessly), then attends each of
    the k query positions to its own causal prefix ``[0, lengths + i]``
    via ``kernels.ops.paged_verify`` — the Mosaic multi-query kernel for
    ``impl='pallas'``, the historical gathered path for ``'jnp'``.  int8
    pools take the gathered dequant path regardless of ``impl`` (draft
    depths are too small to earn a dedicated quant kernel).  Returns
    ``(out, pages)``.
    """
    n, kk, _ = x.shape
    q, k, v = _project_qkv(params, x, x, cfg)
    positions = lengths[:, None] + jnp.arange(kk)[None, :]        # (n, k)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    bs = pages["k"].shape[1]
    blk = jnp.take_along_axis(tables, positions // bs, axis=1)    # (n, k)
    off = positions % bs
    pages = _scatter_kv_rows(pages, blk, off, k, v)
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref
    attn_impl = {"fused": "pallas",
                 "fused_interpret": "pallas_interpret"}.get(impl, impl)
    if "k_scale" in pages:
        out = kref.paged_verify_quant_ref(
            q, pages["k"], pages["v"], pages["k_scale"], pages["v_scale"],
            tables, lengths, window=window)
    else:
        out = kops.paged_verify(q, pages["k"], pages["v"], tables, lengths,
                                window=window, impl=attn_impl)
    out = out.reshape(n, kk, cfg.n_heads * cfg.head_dim).astype(x.dtype)
    return out @ params["wo"].astype(x.dtype), pages


def init_kv_cache(cfg, batch: int, max_seq: int, n_layers: Optional[int] = None,
                  dtype=None) -> dict:
    """Stacked (layers-first) KV cache for decode.

    ``cfg.kv_cache_dtype='float8_e4m3fn'`` halves cache residency (the
    dominant HBM term for decode_32k on the 30B+ models) at serving-standard
    precision cost; values are cast on write and upcast on read."""
    L = n_layers if n_layers is not None else cfg.n_layers
    dtype = dtype if dtype is not None else jnp.dtype(cfg.kv_cache_dtype)
    shape = (L, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "index": jnp.zeros((), jnp.int32)}


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_swiglu(key, cfg, d_ff: Optional[int] = None) -> Params:
    d = cfg.d_model
    f = d_ff if d_ff is not None else cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (d, f), d, cfg.param_dtype),
        "w_up": dense_init(ks[1], (d, f), d, cfg.param_dtype),
        "w_down": dense_init(ks[2], (f, d), f, cfg.param_dtype),
    }


def swiglu(params: Params, x: jnp.ndarray, use_kernel: bool = False) -> jnp.ndarray:
    dt = x.dtype
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.swiglu(x, params["w_gate"].astype(dt),
                           params["w_up"].astype(dt),
                           params["w_down"].astype(dt))
    g = x @ params["w_gate"].astype(dt)
    u = x @ params["w_up"].astype(dt)
    return (jax.nn.silu(g) * u) @ params["w_down"].astype(dt)


def init_gelu_mlp(key, cfg, d_ff: Optional[int] = None) -> Params:
    d = cfg.d_model
    f = d_ff if d_ff is not None else cfg.d_ff
    ks = jax.random.split(key, 2)
    return {
        "w_in": dense_init(ks[0], (d, f), d, cfg.param_dtype),
        "b_in": jnp.zeros((f,), cfg.param_dtype),
        "w_out": dense_init(ks[1], (f, d), f, cfg.param_dtype),
        "b_out": jnp.zeros((d,), cfg.param_dtype),
    }


def gelu_mlp(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    dt = x.dtype
    h = jax.nn.gelu(x @ params["w_in"].astype(dt) + params["b_in"].astype(dt))
    return h @ params["w_out"].astype(dt) + params["b_out"].astype(dt)


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d: int, dtype=jnp.float32) -> Params:
    return {"table": embed_init(key, (vocab, d), dtype)}


def embed(params: Params, tokens: jnp.ndarray, dtype) -> jnp.ndarray:
    return params["table"].astype(dtype)[tokens]


def unembed(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    """Tied LM head: logits in f32 for a stable softmax-xent."""
    return jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32),
                      params["table"].astype(jnp.float32))
