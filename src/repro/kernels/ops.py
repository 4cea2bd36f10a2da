"""jit'd public wrappers around the Pallas kernels.

On the CPU dev container kernels run with ``interpret=True`` (the Pallas
interpreter executes the kernel body faithfully); on TPU the same call sites
compile to Mosaic.  ``repro.models.layers`` routes here when
``cfg.attn_impl`` selects the kernel path.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.fused_decode import fused_decode_layer as _fused_layer
from repro.kernels.paged_attention import (paged_attention_lanes,
                                           paged_attention_quant_lanes,
                                           paged_verify_lanes)
from repro.kernels.rmsnorm import rms_norm_2d
from repro.kernels.ssd_scan import ssd_scan_bshpn
from repro.kernels.swiglu import swiglu_2d

_ON_TPU = jax.default_backend() == "tpu"


def _interp(explicit):
    return (not _ON_TPU) if explicit is None else explicit


@partial(jax.jit, static_argnames=("causal", "window", "interpret",
                                   "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    interpret=None, block_q: int = 128, block_k: int = 128):
    """q: (b, sq, nh, hd); k/v: (b, sk, nkv, hd) — layer-layout entry point."""
    out = flash_attention_bhsd(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=_interp(interpret))
    return out.transpose(0, 2, 1, 3)


def default_paged_impl() -> str:
    """Engine-facing policy: the Mosaic kernel on TPU, the pure-jnp gather
    fallback elsewhere (the Pallas interpreter is faithful but far too slow
    to decode through; it is exercised by tests/test_kernels.py)."""
    return "pallas" if _ON_TPU else "jnp"


@partial(jax.jit, static_argnames=("window", "impl"))
def paged_attention(q, k_pages, v_pages, tables, lengths, *,
                    window=None, impl: str = "jnp"):
    """Single-token attention through a block table.

    q: (n, nh, hd); k/v_pages: (P, bs, nkv, hd); tables: (n, B) physical
    block ids (pad unused entries with a valid block — they are masked);
    lengths: (n,) valid rows per lane including the current token.
    ``impl``: 'jnp' | 'pallas' | 'pallas_interpret'.
    """
    if impl == "jnp":
        return ref.paged_attention_ref(q, k_pages, v_pages, tables, lengths,
                                       window=window)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"paged_attention impl={impl!r}: expected "
                         "'jnp', 'pallas', or 'pallas_interpret'")
    return paged_attention_lanes(q, k_pages, v_pages, tables, lengths,
                                 window=window,
                                 interpret=(impl == "pallas_interpret"))


@partial(jax.jit, static_argnames=("window", "impl"))
def paged_verify(q, k_pages, v_pages, tables, lengths, *,
                 window=None, impl: str = "jnp"):
    """Multi-query (speculative verify) attention through a block table.

    q: (n, k, nh, hd) — all k draft positions per lane, already scattered
    into the pages; tables/lengths as `paged_attention` except ``lengths``
    counts rows committed BEFORE the round (query ``i`` attends through
    logical row ``lengths + i``).  ``impl``: 'jnp' (gathered fallback,
    the historical path) | 'pallas' | 'pallas_interpret'.
    """
    if impl == "jnp":
        return ref.paged_verify_ref(q, k_pages, v_pages, tables, lengths,
                                    window=window)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"paged_verify impl={impl!r}: expected "
                         "'jnp', 'pallas', or 'pallas_interpret'")
    return paged_verify_lanes(q, k_pages, v_pages, tables, lengths,
                              window=window,
                              interpret=(impl == "pallas_interpret"))


@partial(jax.jit, static_argnames=("window", "impl"))
def paged_attention_quant(q, k_pages, v_pages, k_scales, v_scales,
                          tables, lengths, *, window=None,
                          impl: str = "jnp"):
    """int8-KV single-token attention: pages are int8 with per-row f32
    scales (`ref.quantize_kv` layout); dequantization happens inside the
    kernel (or on the gathered rows for the jnp fallback)."""
    if impl == "jnp":
        return ref.paged_attention_quant_ref(
            q, k_pages, v_pages, k_scales, v_scales, tables, lengths,
            window=window)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"paged_attention_quant impl={impl!r}: expected "
                         "'jnp', 'pallas', or 'pallas_interpret'")
    return paged_attention_quant_lanes(
        q, k_pages, v_pages, k_scales, v_scales, tables, lengths,
        window=window, interpret=(impl == "pallas_interpret"))


@partial(jax.jit, static_argnames=("window", "eps", "impl"))
def fused_decode_layer(h, q, k_pages, v_pages, tables, lengths, wo,
                       mlp_scale, w_gate, w_up, w_down, *, window=None,
                       eps: float = 1e-6, impl: str = "jnp"):
    """Fused paged decode layer: attention through the block table + wo
    projection + residual + RMSNorm + SwiGLU + residual, one launch per
    layer (see `fused_decode.fused_decode_layer`).  The jnp fallback
    composes the same epilogue from the oracles."""
    if impl == "jnp":
        return ref.fused_decode_layer_ref(
            h, q, k_pages, v_pages, tables, lengths, wo, mlp_scale,
            w_gate, w_up, w_down, window=window, eps=eps)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"fused_decode_layer impl={impl!r}: expected "
                         "'jnp', 'pallas', or 'pallas_interpret'")
    return _fused_layer(h, q, k_pages, v_pages, tables, lengths, wo,
                        mlp_scale, w_gate, w_up, w_down, window=window,
                        eps=eps, interpret=(impl == "pallas_interpret"))


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, log_a, b_coef, c_coef, *, chunk: int = 256,
             initial_state=None, interpret=None):
    y = ssd_scan_bshpn(x, log_a, b_coef, c_coef, chunk=chunk,
                       interpret=_interp(interpret))
    return y, None   # kernel path does not export final state (training)


@partial(jax.jit, static_argnames=("eps", "interpret"))
def rms_norm(x, w, *, eps: float = 1e-6, interpret=None):
    shape = x.shape
    y = rms_norm_2d(x.reshape(-1, shape[-1]), w, eps=eps,
                    interpret=_interp(interpret))
    return y.reshape(shape)


@partial(jax.jit, static_argnames=("interpret",))
def swiglu(x, w_gate, w_up, w_down, *, interpret=None):
    shape = x.shape
    y = swiglu_2d(x.reshape(-1, shape[-1]), w_gate, w_up, w_down,
                  interpret=_interp(interpret))
    return y.reshape(*shape[:-1], w_down.shape[-1])
