"""Pallas TPU paged attention through a block-table KV cache.

The serving engine stores K/V in fixed-size physical blocks
(``(n_blocks, block_size, n_kv_heads, head_dim)`` pages); each decode lane
owns a *logical* sequence named by a block table row.  The kernel reads K/V
straight through the table — grid ``(lane, logical_block)`` with the block
dimension innermost so the running online-softmax scratch ``(m, l, acc)``
carries across it, exactly like the flash kernel — and the table is a
scalar-prefetch operand, so the physical block id feeds the K/V
``BlockSpec`` index maps and no gathered contiguous copy of the cache is
ever materialized (the whole point of paging: the contiguous gather would
cost a ``max_seq``-sized copy per lane per step).

One grid step takes every kv head of one physical block: the page block is
``(1, block_size, n_kv_heads, head_dim)``, whose last two dims are the
array's own, as the Mosaic lowering requires.  Queries arrive grouped per kv
head, ``(n, n_kv_heads, rows, head_dim)``, and the kernel runs one batched
matmul over the kv heads, so repeated K/V heads are never materialized.

One kernel serves single-token decode (``rows = groups``) and the
speculative-verify multi-query case (``rows = k * groups``): row ``r`` of
a lane sits at logical position ``starts[lane] + r // groups`` and attends
causally through it.  Logical blocks past a lane's extent are masked to
``NEG_INF`` (their table entries point at the reserved garbage block 0, a
valid physical index), so stale or unallocated pages contribute exactly
zero attention weight.  int8 pools pass per-row scales, and dequantization
is folded into the scores and probabilities, so no f32 copy of the cache is
ever materialized.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def init_softmax_scratch(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def attend_block(q, k_ref, v_ref, q_start, b, m_scr, l_scr, acc_scr, *,
                 scale: float, groups: int, window,
                 k_scale_ref=None, v_scale_ref=None):
    """One online-softmax step over one physical block, all kv heads.

    q: (nkv, rows, hd) f32; k/v_ref: (1, bs, nkv, hd) page blocks (plus
    (1, bs, nkv) per-row scales for int8 pools); q_start: logical position
    of row 0; b: logical block index.  Scratch is (nkv, rows, 1) for m and
    l and (nkv, rows, hd) for the accumulator."""
    nkv, rows, _ = q.shape
    block_size = k_ref.shape[1]
    k = jnp.transpose(k_ref[0].astype(jnp.float32), (1, 0, 2))  # (nkv,bs,hd)
    v = jnp.transpose(v_ref[0].astype(jnp.float32), (1, 0, 2))
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,)))) * scale    # (nkv, rows, bs)
    if k_scale_ref is not None:
        s = s * k_scale_ref[0].T[:, None, :]

    shape = (nkv, rows, block_size)
    k_pos = b * block_size + jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1) // groups
    mask = k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    if v_scale_ref is not None:
        p = p * v_scale_ref[0].T[:, None, :]
    pv = jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))))            # (nkv, rows, hd)
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = m_cur


def softmax_result(l_scr, acc_scr):
    return acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)


def _paged_kernel(tables_ref, starts_ref, q_ref, k_ref, v_ref, *refs,
                  scale: float, groups: int, window, quant: bool):
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = refs
    lane = pl.program_id(0)
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        init_softmax_scratch(m_scr, l_scr, acc_scr)

    attend_block(q_ref[0].astype(jnp.float32), k_ref, v_ref,
                 starts_ref[lane], b, m_scr, l_scr, acc_scr, scale=scale,
                 groups=groups, window=window, k_scale_ref=ks_ref,
                 v_scale_ref=vs_ref)

    @pl.when(b == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = softmax_result(l_scr, acc_scr).astype(o_ref.dtype)


def _paged_call(qg, k_pages, v_pages, tables, starts, scales=(), *,
                groups: int, window, interpret: bool):
    """qg: (n, nkv, rows, hd) queries grouped per kv head; starts: (n,)
    logical position of each lane's row 0.  Returns qg-shaped output."""
    n, nkv, rows, hd = qg.shape
    block_size = k_pages.shape[1]
    n_blocks = tables.shape[1]
    kernel = functools.partial(_paged_kernel, scale=1.0 / math.sqrt(hd),
                               groups=groups, window=window,
                               quant=bool(scales))
    q_spec = pl.BlockSpec((1, nkv, rows, hd),
                          lambda i, b, t, st: (i, 0, 0, 0))
    page_spec = pl.BlockSpec((1, block_size, nkv, hd),
                             lambda i, b, t, st: (t[i, b], 0, 0, 0))
    scale_spec = pl.BlockSpec((1, block_size, nkv),
                              lambda i, b, t, st: (t[i, b], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # tables, starts
        grid=(n, n_blocks),
        in_specs=[q_spec, page_spec, page_spec] + [scale_spec] * len(scales),
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((nkv, rows, 1), jnp.float32),    # running max m
            pltpu.VMEM((nkv, rows, 1), jnp.float32),    # running denom l
            pltpu.VMEM((nkv, rows, hd), jnp.float32),   # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, qg.dtype),
        interpret=interpret,
    )(tables.astype(jnp.int32), starts.astype(jnp.int32), qg,
      k_pages, v_pages, *(s.astype(jnp.float32) for s in scales))


def paged_attention_lanes(q, k_pages, v_pages, tables, lengths, *,
                          window=None, interpret: bool = False):
    """q: (n, nh, hd); k/v_pages: (P, bs, nkv, hd); tables: (n, B) physical
    block ids (every entry must be a valid index — pad with the garbage
    block); lengths: (n,) valid rows per lane INCLUDING the current token.
    Returns (n, nh, hd) in q's dtype."""
    n, nh, hd = q.shape
    nkv = k_pages.shape[2]
    assert nh % nkv == 0
    groups = nh // nkv
    out = _paged_call(q.reshape(n, nkv, groups, hd), k_pages, v_pages,
                      tables, lengths - 1, groups=groups, window=window,
                      interpret=interpret)
    return out.reshape(n, nh, hd)


def paged_attention_quant_lanes(q, k_pages, v_pages, k_scales, v_scales,
                                tables, lengths, *,
                                window=None, interpret: bool = False):
    """int8-KV variant of `paged_attention_lanes`: k/v_pages are
    (P, bs, nkv, hd) int8, k/v_scales are (P, bs, nkv) f32 per-row
    symmetric scales (`ref.quantize_kv`).  Scale blocks ride the same
    table-driven index maps as the pages.  Returns (n, nh, hd) in q's
    dtype."""
    n, nh, hd = q.shape
    nkv = k_pages.shape[2]
    assert nh % nkv == 0
    groups = nh // nkv
    out = _paged_call(q.reshape(n, nkv, groups, hd), k_pages, v_pages,
                      tables, lengths - 1, (k_scales, v_scales),
                      groups=groups, window=window, interpret=interpret)
    return out.reshape(n, nh, hd)


def paged_verify_lanes(q, k_pages, v_pages, tables, lengths, *,
                       window=None, interpret: bool = False):
    """Multi-query (speculative verify) attention.  q: (n, k, nh, hd)
    roped queries whose K/V rows are already scattered into the pages;
    lengths: (n,) rows committed BEFORE this verify round, so query ``i``
    attends through row ``lengths + i``.  Returns (n, k, nh, hd)."""
    n, kk, nh, hd = q.shape
    nkv = k_pages.shape[2]
    assert nh % nkv == 0
    groups = nh // nkv
    qg = q.reshape(n, kk, nkv, groups, hd).transpose(0, 2, 1, 3, 4)
    out = _paged_call(qg.reshape(n, nkv, kk * groups, hd), k_pages, v_pages,
                      tables, lengths, groups=groups, window=window,
                      interpret=interpret)
    out = out.reshape(n, nkv, kk, groups, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(n, kk, nh, hd)
