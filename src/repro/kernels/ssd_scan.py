"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Grid ``(batch, head, chunk)`` with the chunk dimension innermost; the
``(p, n)`` inter-chunk state lives in VMEM scratch and carries across chunk
steps — the hardware-native expression of "quadratic within a chunk, linear
recurrence across chunks".  Per-step VMEM working set with Q=256, p=64,
n=128: x (Q,p) + B,C (Q,n) + decay (Q,Q) + state (p,n) ≈ 0.5 MB f32.
All contraction dims (Q, p, n) are MXU-tile friendly.

Also serves mLSTM (matrix-memory) since its recurrence is the same SSD form
with per-head scalar decay — see repro/models/ssm.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, o_ref, state_scr, *,
                chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)             # (Q, p)
    a = a_ref[0].astype(jnp.float32)             # (1, Q)
    b = b_ref[0].astype(jnp.float32)             # (Q, n)
    c = c_ref[0].astype(jnp.float32)             # (Q, n)
    n = b.shape[1]

    # prefix sums as matmuls with triangular / all-ones matrices (Mosaic has
    # neither cumsum nor a scalar broadcast out of a vector); HIGHEST keeps
    # the log-decay sums at f32 accuracy
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)

    def sums(m):
        return jnp.dot(a, m.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)

    a_row = sums(row <= col)                     # (1, Q) inclusive cumsum
    a_col = a_row.T                              # (Q, 1)
    a_tot = sums(jnp.ones((chunk, n), bool))     # (1, n) chunk total

    # intra-chunk (quadratic in Q)
    li = jnp.where(row >= col, a_col - a_row, -1e30)   # mask BEFORE exp
    decay = jnp.exp(li)
    scores = (c @ b.T) * decay                   # (Q, Q)
    y = scores @ x                               # (Q, p)

    # inter-chunk contribution from the carried state
    state = state_scr[...]                       # (p, n)
    y = y + jnp.exp(a_col) * (c @ state.T)

    # state update for the next chunk: row i decays by the sum after it
    w = jnp.exp(sums(row > col)).T               # (Q, 1)
    state_scr[...] = jnp.exp(a_tot) * state + (x * w).T @ b

    o_ref[0] = y.astype(o_ref.dtype)


def ssd_scan_bshpn(x, log_a, b_coef, c_coef, *, chunk: int,
                   interpret: bool = False):
    """x: (b, s, h, p); log_a: (b, s, h); b/c: (b, s, h, n) -> y like x.

    Reshapes to (b, h, nc, Q, ·) blocks and runs the chunk-sequential grid.
    """
    bsz, s, h, p = x.shape
    n = b_coef.shape[-1]
    assert s % chunk == 0
    nc = s // chunk

    xt = x.transpose(0, 2, 1, 3).reshape(bsz, h, nc, chunk, p)
    at = log_a.transpose(0, 2, 1).reshape(bsz, h, nc, chunk)
    bt = b_coef.transpose(0, 2, 1, 3).reshape(bsz, h, nc, chunk, n)
    ct = c_coef.transpose(0, 2, 1, 3).reshape(bsz, h, nc, chunk, n)
    # fold (b, h) since the grid treats them identically
    xt = xt.reshape(bsz * h, nc, chunk, p)
    at = at.reshape(bsz * h, nc, 1, chunk)   # (1, chunk) blocks: tileable
    bt = bt.reshape(bsz * h, nc, chunk, n)
    ct = ct.reshape(bsz * h, nc, chunk, n)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(bsz * h, nc),
        in_specs=[
            pl.BlockSpec((1, None, chunk, p), lambda bh, ci: (bh, ci, 0, 0)),
            pl.BlockSpec((1, None, 1, chunk),
                         lambda bh, ci: (bh, ci, 0, 0)),
            pl.BlockSpec((1, None, chunk, n), lambda bh, ci: (bh, ci, 0, 0)),
            pl.BlockSpec((1, None, chunk, n), lambda bh, ci: (bh, ci, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, None, chunk, p),
                               lambda bh, ci: (bh, ci, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz * h, nc, chunk, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xt, at, bt, ct)
    return out.reshape(bsz, h, s, p).transpose(0, 2, 1, 3)
