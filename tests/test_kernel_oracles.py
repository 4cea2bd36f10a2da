"""Differential kernel-oracle harness: every Pallas entry point in
``repro.kernels`` fuzzed against its pure-jnp oracle in ``kernels/ref.py``.

The decode hot path now runs three compounding kernel optimizations
(multi-query paged verify, the fused paged decode layer, int8-quantized
KV pages), and each is only trustworthy relative to a slow, obviously-
correct reference.  This harness is the gate:

* hypothesis sweeps randomize shapes, GQA group counts, block sizes,
  table layouts, lengths, windows, and dtypes per kernel, asserting
  ``allclose`` against the oracle under per-kernel tolerances;
* exact edge cases pin the block-table conventions the kernels must
  honor — lengths on a block boundary, garbage-block / stale-row
  invisibility (poisoned pages change nothing), and single-token lanes;
* the int8 KV path gets round-trip properties (zero rows exact, error
  bounded by half a quantization step) plus teacher-forced decode
  logits against the fp pool for both paged families (dense, vlm),
  within a stated bf16 bound, with the max logit drift reported.

All Pallas launches run in interpret mode so the harness is hermetic on
CPU hosts; on TPU the same entry points compile for real.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref

F32_TOL = 2e-5      # elementwise/attention kernels, f32
BF16_TOL = 2e-2     # bf16 rounding dominates
MM_TOL = 2e-4       # kernels ending in matmul chains (swiglu, fused layer)


def _tol(dtype, f32=F32_TOL):
    return f32 if dtype == jnp.float32 else BF16_TOL


def _close(out, exp, tol):
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=tol, atol=tol)


def _pages(seed, P, bs, nkv, hd, dtype=jnp.float32):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k1, (P, bs, nkv, hd), dtype),
            jax.random.normal(k2, (P, bs, nkv, hd), dtype))


def _tables(rng, n, B, P):
    """Distinct physical blocks per lane; never the garbage block 0."""
    return jnp.asarray(
        (rng.permutation(P - 1)[: n * B] + 1).reshape(n, B), jnp.int32)


# ---------------------------------------------------------------------------
# fuzz sweeps: one property per kernel entry point
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10**6), st.integers(1, 2),
       st.sampled_from([64, 96, 128]), st.sampled_from([1, 2]),
       st.sampled_from([1, 2]), st.sampled_from([16, 32, 64]),
       st.booleans(), st.sampled_from([None, 32]),
       st.sampled_from([jnp.float32, jnp.bfloat16]))
def test_fuzz_flash_attention(seed, b, s, nkv, groups, hd, causal, window,
                              dtype):
    nh = nkv * groups
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, nh, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, nkv, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, nkv, hd), dtype)
    win = window if causal else None
    out = ops.flash_attention(q, k, v, causal=causal, window=win,
                              interpret=True, block_q=64, block_k=64)
    exp = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal,
        window=win).transpose(0, 2, 1, 3)
    _close(out, exp, _tol(dtype))


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10**6), st.integers(1, 200),
       st.sampled_from([64, 128, 256]),
       st.sampled_from([jnp.float32, jnp.bfloat16]))
def test_fuzz_rms_norm(seed, rows, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(ks[0], (rows, d), dtype)
    w = jax.random.normal(ks[1], (d,)) * 0.1 + 1.0
    _close(ops.rms_norm(x, w, interpret=True), ref.rms_norm_ref(x, w),
           _tol(dtype, f32=1e-5))


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10**6), st.integers(1, 128),
       st.sampled_from([64, 128]), st.sampled_from([128, 300]))
def test_fuzz_swiglu(seed, m, d, f):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (m, d))
    wg = jax.random.normal(ks[1], (d, f)) * 0.05
    wu = jax.random.normal(ks[2], (d, f)) * 0.05
    wd = jax.random.normal(ks[3], (f, d)) * 0.05
    _close(ops.swiglu(x, wg, wu, wd, interpret=True),
           ref.swiglu_ref(x, wg, wu, wd), MM_TOL)


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 10**6), st.integers(1, 2), st.integers(1, 3),
       st.sampled_from([8, 16]), st.sampled_from([8, 16]),
       st.sampled_from([32, 64]))
def test_fuzz_ssd_scan(seed, b, h, p, n, chunk):
    s = chunk * (1 + seed % 3)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    la = -jnp.abs(jax.random.normal(ks[1], (b, s, h))) * 0.1
    bc = jax.random.normal(ks[2], (b, s, h, n)) * 0.3
    cc = jax.random.normal(ks[3], (b, s, h, n)) * 0.3
    y, _ = ops.ssd_scan(x, la, bc, cc, chunk=chunk, interpret=True)
    _close(y, ref.ssd_scan_ref(x, la, bc, cc, chunk=chunk), MM_TOL)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10**6), st.integers(1, 4), st.sampled_from([1, 2]),
       st.sampled_from([1, 2, 4]), st.sampled_from([16, 32, 64]),
       st.sampled_from([4, 8]), st.integers(1, 4),
       st.sampled_from([None, 5]),
       st.sampled_from([jnp.float32, jnp.bfloat16]))
def test_fuzz_paged_attention(seed, n, nkv, groups, hd, bs, B, window,
                              dtype):
    rng = np.random.default_rng(seed)
    P = n * B + 1 + int(rng.integers(0, 3))
    kp, vp = _pages(seed, P, bs, nkv, hd, dtype)
    q = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (n, nkv * groups, hd), dtype)
    tables = _tables(rng, n, B, P)
    lengths = jnp.asarray(rng.integers(1, B * bs + 1, n), jnp.int32)
    out = ops.paged_attention(q, kp, vp, tables, lengths, window=window,
                              impl="pallas_interpret")
    exp = ref.paged_attention_ref(q, kp, vp, tables, lengths, window=window)
    _close(out, exp, _tol(dtype))


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10**6), st.integers(1, 3), st.integers(1, 4),
       st.sampled_from([1, 2]), st.sampled_from([1, 2, 4]),
       st.sampled_from([16, 32]), st.sampled_from([4, 8]),
       st.integers(1, 3), st.sampled_from([None, 6]),
       st.sampled_from([jnp.float32, jnp.bfloat16]))
def test_fuzz_paged_verify(seed, n, kk, nkv, groups, hd, bs, B, window,
                           dtype):
    """Multi-query verify: all k draft rows scored through block tables
    in one launch == the gathered multi-query oracle.  ``lengths`` is the
    rows committed BEFORE the round (draft row j attends through
    lengths + j), so the sweep includes zero-prefix lanes."""
    rng = np.random.default_rng(seed)
    B = max(B, -(-kk // bs))                     # table wide enough for kk
    P = n * B + 1 + int(rng.integers(0, 3))
    kp, vp = _pages(seed, P, bs, nkv, hd, dtype)
    q = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (n, kk, nkv * groups, hd), dtype)
    tables = _tables(rng, n, B, P)
    lengths = jnp.asarray(rng.integers(0, B * bs - kk + 1, n), jnp.int32)
    out = ops.paged_verify(q, kp, vp, tables, lengths, window=window,
                           impl="pallas_interpret")
    exp = ref.paged_verify_ref(q, kp, vp, tables, lengths, window=window)
    _close(out, exp, _tol(dtype))


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10**6), st.integers(1, 3), st.sampled_from([1, 2]),
       st.sampled_from([1, 2, 4]), st.sampled_from([16, 32, 64]),
       st.sampled_from([4, 8]), st.integers(1, 3),
       st.sampled_from([None, 5]))
def test_fuzz_paged_attention_quant(seed, n, nkv, groups, hd, bs, B,
                                    window):
    """int8 decode attention: in-kernel dequant == gathered dequant
    oracle, over randomly quantized pages."""
    rng = np.random.default_rng(seed)
    P = n * B + 1 + int(rng.integers(0, 3))
    kf, vf = _pages(seed, P, bs, nkv, hd)
    kq, ks_ = ref.quantize_kv(kf)
    vq, vs = ref.quantize_kv(vf)
    q = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (n, nkv * groups, hd), jnp.float32)
    tables = _tables(rng, n, B, P)
    lengths = jnp.asarray(rng.integers(1, B * bs + 1, n), jnp.int32)
    out = ops.paged_attention_quant(q, kq, vq, ks_, vs, tables, lengths,
                                    window=window, impl="pallas_interpret")
    exp = ref.paged_attention_quant_ref(q, kq, vq, ks_, vs, tables,
                                        lengths, window=window)
    _close(out, exp, F32_TOL)


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10**6), st.integers(1, 3), st.sampled_from([1, 2]),
       st.sampled_from([1, 2]), st.sampled_from([16, 32]),
       st.sampled_from([4, 8]), st.integers(1, 3),
       st.sampled_from([None, 6]), st.sampled_from([64, 96]))
def test_fuzz_fused_decode_layer(seed, n, nkv, groups, hd, bs, B, window,
                                 d):
    """Fused paged decode layer (attention + wo + RMSNorm + SwiGLU +
    residuals, one launch) == the composed oracle."""
    rng = np.random.default_rng(seed)
    nh, f = nkv * groups, 2 * d
    P = n * B + 1 + int(rng.integers(0, 3))
    kp, vp = _pages(seed, P, bs, nkv, hd)
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 7)
    h = jax.random.normal(ks[0], (n, d))
    q = jax.random.normal(ks[1], (n, nh, hd))
    wo = jax.random.normal(ks[2], (nh * hd, d)) * 0.05
    mlp_scale = jax.random.normal(ks[3], (d,)) * 0.1 + 1.0
    wg = jax.random.normal(ks[4], (d, f)) * 0.05
    wu = jax.random.normal(ks[5], (d, f)) * 0.05
    wd = jax.random.normal(ks[6], (f, d)) * 0.05
    tables = _tables(rng, n, B, P)
    lengths = jnp.asarray(rng.integers(1, B * bs + 1, n), jnp.int32)
    out = ops.fused_decode_layer(h, q, kp, vp, tables, lengths, wo,
                                 mlp_scale, wg, wu, wd, window=window,
                                 impl="pallas_interpret")
    exp = ref.fused_decode_layer_ref(h, q, kp, vp, tables, lengths, wo,
                                     mlp_scale, wg, wu, wd, window=window)
    _close(out, exp, MM_TOL)


# ---------------------------------------------------------------------------
# exact block-table edge cases (the conventions fuzz can miss)
# ---------------------------------------------------------------------------

_EDGE = dict(n=3, nkv=2, groups=2, hd=32, bs=4, B=3)


def _edge_fixture(seed=13, kk=0):
    e = _EDGE
    P = e["n"] * e["B"] + 2
    kp, vp = _pages(seed, P, e["bs"], e["nkv"], e["hd"])
    nh = e["nkv"] * e["groups"]
    shape = (e["n"], kk, nh, e["hd"]) if kk else (e["n"], nh, e["hd"])
    q = jax.random.normal(jax.random.PRNGKey(seed + 1), shape, jnp.float32)
    tables = _tables(np.random.default_rng(seed), e["n"], e["B"], P)
    return q, kp, vp, tables


def test_edge_block_boundary_lengths():
    """Lengths exactly on block boundaries: one full block, mid-table
    boundary, and the whole table — off-by-one in the block loop's mask
    shows up here first."""
    e = _EDGE
    q, kp, vp, tables = _edge_fixture()
    lengths = jnp.asarray([e["bs"], 2 * e["bs"], e["B"] * e["bs"]],
                          jnp.int32)
    _close(ops.paged_attention(q, kp, vp, tables, lengths,
                               impl="pallas_interpret"),
           ref.paged_attention_ref(q, kp, vp, tables, lengths), F32_TOL)
    qv, kp, vp, tables = _edge_fixture(kk=2)
    lv = jnp.asarray([e["bs"], 2 * e["bs"] - 2, e["bs"] - 1], jnp.int32)
    _close(ops.paged_verify(qv, kp, vp, tables, lv,
                            impl="pallas_interpret"),
           ref.paged_verify_ref(qv, kp, vp, tables, lv), F32_TOL)


def test_edge_garbage_block_and_stale_rows_invisible():
    """Poisoning the garbage block (0) and every row past each lane's
    length must not move the kernel's output at all — table entries past
    the live extent point at block 0, and attention masks the rest."""
    q, kp, vp, tables = _edge_fixture()
    # lane 2's table tail points at the garbage block (short sequence)
    tables = np.asarray(tables).copy()
    tables[2, 1:] = 0
    tables = jnp.asarray(tables)
    lengths = jnp.asarray([5, 9, 3], jnp.int32)
    base = ops.paged_attention(q, kp, vp, tables, lengths,
                               impl="pallas_interpret")
    kp2 = kp.at[0].set(997.0)
    vp2 = vp.at[0].set(-997.0)
    # also trash the masked tail rows of each lane's last live block
    for lane, ln in enumerate([5, 9, 3]):
        blk = int(np.asarray(tables)[lane, ln // _EDGE["bs"]])
        kp2 = kp2.at[blk, ln % _EDGE["bs"]:].set(999.0)
        vp2 = vp2.at[blk, ln % _EDGE["bs"]:].set(999.0)
    out = ops.paged_attention(q, kp2, vp2, tables, lengths,
                              impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(base), np.asarray(out))


def test_edge_garbage_block_invisible_to_verify():
    qv, kp, vp, tables = _edge_fixture(kk=3)
    lengths = jnp.asarray([0, 4, 2], jnp.int32)
    base = ops.paged_verify(qv, kp, vp, tables, lengths,
                            impl="pallas_interpret")
    out = ops.paged_verify(qv, kp.at[0].set(999.0), vp.at[0].set(-999.0),
                           tables, lengths, impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(base), np.asarray(out))


def test_edge_single_token_lanes():
    """Every lane at length 1 (first decode step after a 1-token prompt):
    softmax over a single row must be exact for all three paged kernels."""
    q, kp, vp, tables = _edge_fixture()
    lengths = jnp.asarray([1, 1, 1], jnp.int32)
    _close(ops.paged_attention(q, kp, vp, tables, lengths,
                               impl="pallas_interpret"),
           ref.paged_attention_ref(q, kp, vp, tables, lengths), F32_TOL)
    kq, ks_ = ref.quantize_kv(kp)
    vq, vs = ref.quantize_kv(vp)
    _close(ops.paged_attention_quant(q, kq, vq, ks_, vs, tables, lengths,
                                     impl="pallas_interpret"),
           ref.paged_attention_quant_ref(q, kq, vq, ks_, vs, tables,
                                         lengths), F32_TOL)
    qv, kp, vp, tables = _edge_fixture(kk=1)
    _close(ops.paged_verify(qv, kp, vp, tables, lengths,
                            impl="pallas_interpret"),
           ref.paged_verify_ref(qv, kp, vp, tables, lengths), F32_TOL)


# ---------------------------------------------------------------------------
# int8 KV quantization: round-trip properties + decode token identity
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10**6), st.integers(1, 6), st.sampled_from([16, 64]),
       st.floats(0.01, 100.0))
def test_quant_round_trip_bounded(seed, rows, hd, scale):
    """Per-row symmetric int8: |x - dq(q(x))| <= scale/2 elementwise
    (half a quantization step), for any row magnitude."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (rows, hd)) * scale
    q, s = ref.quantize_kv(x)
    dq = ref.dequantize_kv(q, s)
    bound = np.asarray(s)[:, None] / 2 + 1e-12
    assert (np.abs(np.asarray(x) - np.asarray(dq)) <= bound).all()
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32


def test_quant_zero_rows_exact():
    """All-zero rows (the garbage block, freshly allocated pages) must
    round-trip EXACTLY — scale clamps at eps instead of dividing by 0."""
    q, s = ref.quantize_kv(jnp.zeros((3, 4, 2, 16)))
    np.testing.assert_array_equal(np.asarray(ref.dequantize_kv(q, s)), 0.0)


# Teacher-forced decode comparisons: both paths are fed the same seeded
# tokens, so one rounding difference cannot change what later steps see,
# and logits are compared under a bound instead of argmax (on random
# weights the top two logits sit ~1e-2 apart, so argmax flips on rounding).
# The smoke stacks compute in bf16, whose step at the ~1.3 logit peak is
# 2**-7 ~ 8e-3; 5e-2 allows ~6 such steps accumulated through the layers.
DECODE_LOGIT_TOL = 5e-2


def _paged_family_logits(cfg, params, kv_dtype, impl="jnp", steps=12,
                         seed=5):
    """Last-position logits per step, (steps, n, vocab), from teacher-forced
    paged decode steps growing the pool from empty (every step scatters
    then attends)."""
    from repro.models import api
    n, bs, B = 2, 4, (steps + 3) // 4 + 1
    P = n * B + 1
    pages = api.init_kv_pages(cfg, P, bs, kv_dtype)
    rng = np.random.default_rng(seed)
    tables = jnp.asarray(
        (rng.permutation(P - 1)[: n * B] + 1).reshape(n, B), jnp.int32)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (steps, n, 1)),
                         jnp.int32)
    out = []
    for step in range(steps):
        lengths = jnp.full((n,), step, jnp.int32)
        logits, pages = api.paged_decode_step(
            cfg, params, pages, tables, lengths, tokens[step], impl=impl)
        out.append(np.asarray(logits[:, -1], np.float32))
    return np.stack(out)


@pytest.mark.parametrize("model", ["qwen3-0.6b", "llava-next-mistral-7b"])
def test_int8_kv_decode_token_identity(model):
    """int8 KV pages decode like the fp pool on the same tokens, for every
    kv_quant family (dense, vlm): logits agree within DECODE_LOGIT_TOL.
    Per-row int8 rounding is at most amax/254 per element, about one bf16
    step, so the quantized pool adds no more than the stack's own bf16
    rounding.  The max logit delta is reported."""
    from repro.configs import get_config
    from repro.models import api
    cfg = get_config(model, smoke=True)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    fp = _paged_family_logits(cfg, params, None)
    q = _paged_family_logits(cfg, params, "int8")
    drift = float(np.max(np.abs(fp - q)))
    print(f"\n[kv-quant drift] {model}: max |logit delta| = {drift:.4f} "
          f"over {fp.shape[0]} steps")
    np.testing.assert_allclose(q, fp, rtol=DECODE_LOGIT_TOL,
                               atol=DECODE_LOGIT_TOL)


def test_int8_kv_default_stays_fp():
    """Nothing quantizes unless asked: default pools carry no scale
    planes, and the default ServeJob keeps kv_dtype None."""
    from repro.api.jobs import ServeJob
    from repro.configs import get_config
    from repro.models import api
    from repro.serving.paging import BlockPool
    cfg = get_config("qwen3-0.6b", smoke=True)
    assert set(api.init_kv_pages(cfg, 4, 4)) == {"k", "v"}
    assert set(api.init_kv_pages(cfg, 4, 4, "fp")) == {"k", "v"}
    assert set(api.init_kv_pages(cfg, 4, 4, "int8")) \
        == {"k", "v", "k_scale", "v_scale"}
    assert BlockPool(cfg, 4, 4).kv_dtype == "fp"
    assert ServeJob(cfg=cfg).kv_dtype is None
    # and the quantized pool is priced strictly below fp under the same
    # geometry — the whole point of the optimization
    assert api.kv_block_bytes(cfg, 16, "int8") < api.kv_block_bytes(cfg, 16)


def test_int8_kv_rejects_non_quant_family():
    """Families without a declared quantized page layout fail loudly at
    pool construction, not silently at decode."""
    from repro.configs import get_config
    from repro.models import api
    cfg = get_config("mixtral-8x22b", smoke=True)
    with pytest.raises(ValueError, match="int8|kv_quant|paging|paged"):
        api.kv_block_bytes(cfg, 16, "int8")


def test_fused_impl_matches_jnp_paged_decode():
    """impl='fused_interpret' (fused layer kernel per scan step) is
    numerically interchangeable with the jnp paged decode path: on the
    same tokens, logits agree within DECODE_LOGIT_TOL (the fused kernel
    keeps the residual and the norm in f32 where the jnp path rounds them
    to bf16)."""
    from repro.configs import get_config
    from repro.models import api
    cfg = get_config("qwen3-0.6b", smoke=True)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    jnp_logits = _paged_family_logits(cfg, params, None, steps=6, seed=3)
    fused = _paged_family_logits(cfg, params, None, impl="fused_interpret",
                                 steps=6, seed=3)
    np.testing.assert_allclose(fused, jnp_logits, rtol=DECODE_LOGIT_TOL,
                               atol=DECODE_LOGIT_TOL)
