"""Pallas TPU fused paged decode layer.

One launch per layer covers the whole post-projection decode hot path:
paged attention through the block table (all kv heads of a lane in one
program, so the epilogue has the full attention output), the ``wo``
projection + residual add, the MLP RMSNorm, and the SwiGLU block with
its residual.  QKV projection, rope, and the KV row scatter stay
outside — they write the pages the kernel reads.

Grid is ``(lane, logical_block)`` with the block dimension innermost;
the attention step is `paged_attention.attend_block`, so the online-softmax
scratch carries across blocks exactly as in `paged_attention_lanes`.  At
the last block the epilogue runs once per lane with every weight matrix
resident in VMEM (constant index maps, single-buffered, since they never
change block), so the kernel's VMEM limit is raised to hold them.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention import (attend_block,
                                           init_softmax_scratch)

_VMEM_HEADROOM = 16 << 20   # pages, activations and Mosaic's own scratch


def _fused_kernel(tables_ref, lengths_ref, h_ref, q_ref, k_ref, v_ref,
                  wo_ref, scale_ref, wg_ref, wu_ref, wd_ref, o_ref,
                  m_scr, l_scr, acc_scr, *,
                  scale: float, groups: int, window, eps: float):
    lane = pl.program_id(0)
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        init_softmax_scratch(m_scr, l_scr, acc_scr)

    attend_block(q_ref[0].astype(jnp.float32), k_ref, v_ref,
                 lengths_ref[lane] - 1, b, m_scr, l_scr, acc_scr,
                 scale=scale, groups=groups, window=window)

    @pl.when(b == pl.num_programs(1) - 1)
    def _epilogue():
        nkv, _, hd = acc_scr.shape
        h1 = h_ref[0].astype(jnp.float32)                     # (1, d)
        # attention output of head (kv, g) times its rows of wo
        for kv in range(nkv):
            for g in range(groups):
                head = (acc_scr[kv, g:g + 1, :]
                        / jnp.maximum(l_scr[kv, g:g + 1, :], 1e-30))
                row = (kv * groups + g) * hd
                h1 = h1 + _mm(head, wo_ref[row:row + hd, :])
        var = jnp.mean(jnp.square(h1), axis=-1, keepdims=True)
        hn = h1 * jax.lax.rsqrt(var + eps) \
            * scale_ref[...].astype(jnp.float32)
        act = jax.nn.silu(_mm(hn, wg_ref[...])) * _mm(hn, wu_ref[...])
        o_ref[0] = (h1 + _mm(act, wd_ref[...])).astype(o_ref.dtype)


def _mm(x, w):
    """Activation times a weight block in the weight's dtype, f32 result:
    no f32 copy of a weight matrix ever exists in VMEM."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def fused_decode_layer(h, q, k_pages, v_pages, tables, lengths, wo,
                       mlp_scale, w_gate, w_up, w_down, *,
                       window=None, eps: float = 1e-6,
                       interpret: bool = False):
    """h: (n, d) residual stream; q: (n, nh, hd) roped queries whose K/V
    rows are already scattered; k/v_pages: (P, bs, nkv, hd); tables:
    (n, B) physical block ids (pad with the garbage block); lengths: (n,)
    valid rows per lane INCLUDING the current token; wo: (nh*hd, d);
    mlp_scale: (d,); w_gate/w_up: (d, f); w_down: (f, d).  Returns the
    next (n, d) residual in h's dtype."""
    n, nh, hd = q.shape
    _, block_size, nkv, _ = k_pages.shape
    n_blocks = tables.shape[1]
    d = h.shape[1]
    assert nh % nkv == 0
    groups = nh // nkv

    kernel = functools.partial(_fused_kernel, scale=1.0 / math.sqrt(hd),
                               groups=groups, window=window, eps=eps)

    def weight_spec(shape):
        return pl.BlockSpec(shape, lambda i, b, t, le: (0,) * len(shape),
                            pipeline_mode=pl.Buffered(1))

    weights = (wo, mlp_scale.reshape(1, d), w_gate, w_up, w_down)
    weight_bytes = sum(w.size * w.dtype.itemsize for w in weights)
    row_spec = pl.BlockSpec((1, 1, d), lambda i, b, t, le: (i, 0, 0))
    page_spec = pl.BlockSpec((1, block_size, nkv, hd),
                             lambda i, b, t, le: (t[i, b], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # tables, lengths
        grid=(n, n_blocks),
        in_specs=[
            row_spec,
            pl.BlockSpec((1, nkv, groups, hd),
                         lambda i, b, t, le: (i, 0, 0, 0)),
            page_spec, page_spec,
            *(weight_spec(w.shape) for w in weights),
        ],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((nkv, groups, 1), jnp.float32),    # running max m
            pltpu.VMEM((nkv, groups, 1), jnp.float32),    # running denom l
            pltpu.VMEM((nkv, groups, hd), jnp.float32),   # attention acc
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, d), h.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=weight_bytes + _VMEM_HEADROOM),
        interpret=interpret,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), h[:, None],
      q.reshape(n, nkv, groups, hd), k_pages, v_pages, *weights)
    return out[:, 0]
