"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for
a chip that is described, not attached, at the published widths of the
configs that use it.  This catches what interpret mode cannot — block
shapes the Mosaic lowering refuses, unsupported primitives, VMEM
overflows — at no chip time.  Every test asserts the compiled program
carries the Mosaic kernel (``tpu_custom_call``), i.e. no XLA fallback.

The topology is described inside a fixture, never at import, so that
under several pytest workers only the worker running this file loads the
TPU compiler library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops

LANES = 8            # decode lanes of the serving smoke (ServeJob.capacity)
BLOCK_SIZE = 16      # ServeJob.block_size default
MAX_SEQ = 1024
DRAFT_K = 4          # ServeJob.draft_k default


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip cannot be read back without one:
    # keep it out of the persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler library to describe it
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _paged_shapes(cfg):
    blocks = MAX_SEQ // BLOCK_SIZE
    pages = (LANES * blocks + 1, BLOCK_SIZE, cfg.n_kv_heads, cfg.head_dim)
    return pages, (LANES, blocks)


def test_paged_attention_compiles(chip):
    cfg = get_config("qwen3-0.6b")
    pages, tables = _paged_shapes(cfg)
    _compile(chip, lambda q, k, v, t, le: ops.paged_attention(
        q, k, v, t, le, impl="pallas"),
        ((LANES, cfg.n_heads, cfg.head_dim), jnp.bfloat16),
        (pages, jnp.bfloat16), (pages, jnp.bfloat16),
        (tables, jnp.int32), ((LANES,), jnp.int32))


def test_paged_attention_quant_compiles(chip):
    cfg = get_config("qwen3-0.6b")
    pages, tables = _paged_shapes(cfg)
    _compile(chip, lambda q, k, v, ks, vs, t, le: ops.paged_attention_quant(
        q, k, v, ks, vs, t, le, impl="pallas"),
        ((LANES, cfg.n_heads, cfg.head_dim), jnp.bfloat16),
        (pages, jnp.int8), (pages, jnp.int8),
        (pages[:3], jnp.float32), (pages[:3], jnp.float32),
        (tables, jnp.int32), ((LANES,), jnp.int32))


def test_paged_verify_compiles(chip):
    cfg = get_config("qwen3-0.6b")
    pages, tables = _paged_shapes(cfg)
    _compile(chip, lambda q, k, v, t, le: ops.paged_verify(
        q, k, v, t, le, impl="pallas"),
        ((LANES, DRAFT_K, cfg.n_heads, cfg.head_dim), jnp.bfloat16),
        (pages, jnp.bfloat16), (pages, jnp.bfloat16),
        (tables, jnp.int32), ((LANES,), jnp.int32))


def test_fused_decode_layer_compiles(chip):
    cfg = get_config("qwen3-0.6b")
    pages, tables = _paged_shapes(cfg)
    d, f = cfg.d_model, cfg.d_ff
    _compile(chip, lambda h, q, k, v, t, le, wo, s, wg, wu, wd:
             ops.fused_decode_layer(h, q, k, v, t, le, wo, s, wg, wu, wd,
                                    impl="pallas"),
             ((LANES, d), jnp.bfloat16),
             ((LANES, cfg.n_heads, cfg.head_dim), jnp.bfloat16),
             (pages, jnp.bfloat16), (pages, jnp.bfloat16),
             (tables, jnp.int32), ((LANES,), jnp.int32),
             ((cfg.n_heads * cfg.head_dim, d), jnp.bfloat16),
             ((d,), jnp.bfloat16), ((d, f), jnp.bfloat16),
             ((d, f), jnp.bfloat16), ((f, d), jnp.bfloat16))


def test_ssd_scan_compiles(chip):
    from repro.models.ssm import SSM_HEAD_DIM
    cfg = get_config("zamba2-1.2b")
    heads = cfg.ssm_expand * cfg.d_model // SSM_HEAD_DIM
    seq = 2 * cfg.ssm_chunk
    x = ((1, seq, heads, SSM_HEAD_DIM), jnp.float32)
    bc = ((1, seq, heads, cfg.ssm_state), jnp.float32)
    _compile(chip, lambda x_, a, b, c: ops.ssd_scan(
        x_, a, b, c, chunk=cfg.ssm_chunk, interpret=False)[0],
        x, ((1, seq, heads), jnp.float32), bc, bc)


def test_flash_attention_compiles(chip):
    cfg = get_config("bert-large-1b")
    q = ((4, 512, cfg.n_heads, cfg.head_dim), jnp.bfloat16)
    kv = ((4, 512, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    _compile(chip, lambda q_, k, v: ops.flash_attention(
        q_, k, v, causal=cfg.causal, interpret=False), q, kv, kv)


def test_rms_norm_compiles(chip):
    cfg = get_config("qwen3-0.6b")
    _compile(chip, lambda x, w: ops.rms_norm(x, w, interpret=False),
             ((4 * 512, cfg.d_model), jnp.bfloat16),
             ((cfg.d_model,), jnp.float32))


def test_swiglu_compiles(chip):
    cfg = get_config("qwen3-0.6b")
    d, f = cfg.d_model, cfg.d_ff
    _compile(chip, lambda x, wg, wu, wd: ops.swiglu(
        x, wg, wu, wd, interpret=False),
        ((4 * 512, d), jnp.bfloat16), ((d, f), jnp.bfloat16),
        ((d, f), jnp.bfloat16), ((f, d), jnp.bfloat16))
