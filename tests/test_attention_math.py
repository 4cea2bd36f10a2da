"""Attention + recurrence math invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as nn
from repro.models import ssm


def _qkv(b, sq, sk, nh, nkv, hd, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, sq, nh, hd)),
            jax.random.normal(ks[1], (b, sk, nkv, hd)),
            jax.random.normal(ks[2], (b, sk, nkv, hd)))


def test_sdpa_gqa_equals_repeated_kv():
    q, k, v = _qkv(2, 32, 32, 8, 2, 16)
    out = nn.sdpa(q, k, v, causal=True)
    k_rep = jnp.repeat(k, 4, axis=2)
    v_rep = jnp.repeat(v, 4, axis=2)
    out_rep = nn.sdpa(q, k_rep, v_rep, causal=True)
    np.testing.assert_allclose(out, out_rep, rtol=1e-5, atol=1e-5)


def test_sdpa_chunked_matches_dense():
    q, k, v = _qkv(1, 2048, 2048, 2, 2, 16)
    dense = nn._sdpa_dense(
        q.reshape(1, 2048, 2, 1, 16), k, v, 1 / 4.0,
        jnp.arange(2048), jnp.arange(2048), True, None
    ).reshape(1, 2048, 2, 16)
    # force the blockwise path (scores never held whole)
    old = nn._SDPA_DENSE_ELEMS
    nn._SDPA_DENSE_ELEMS = 1024 * 1024
    try:
        chunked = nn.sdpa(q, k, v, causal=True)
    finally:
        nn._SDPA_DENSE_ELEMS = old
    np.testing.assert_allclose(chunked, dense.astype(chunked.dtype),
                               rtol=1e-5, atol=1e-5)


def test_sliding_window_masks_far_tokens():
    q, k, v = _qkv(1, 64, 64, 2, 2, 16)
    w = nn.sdpa(q, k, v, causal=True, window=8)
    # distant value perturbation must not affect outputs beyond the window
    v2 = v.at[:, 0].add(100.0)
    w2 = nn.sdpa(q, k, v2, causal=True, window=8)
    np.testing.assert_allclose(w[:, 16:], w2[:, 16:], rtol=1e-5, atol=1e-5)
    assert not np.allclose(w[:, :8], w2[:, :8])


def test_kv_cache_decode_equals_full_attention():
    cfg = _cfg()
    params = nn.init_attention(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, cfg.d_model))
    full, _ = nn.attention(params, x, cfg, causal=True)
    cache = nn.init_kv_cache(cfg, 2, 16, n_layers=1, dtype=jnp.float32)
    cache = {"k": cache["k"][0], "v": cache["v"][0], "index": cache["index"]}
    outs = []
    for i in range(12):
        pos = jnp.full((2, 1), i, jnp.int32)
        o, cache = nn.attention(params, x[:, i:i + 1], cfg,
                                positions=pos, causal=True, kv_cache=cache)
        outs.append(o[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(dec, full, rtol=1e-4, atol=1e-4)


def _cfg():
    from repro.configs.base import ArchConfig
    return ArchConfig(name="t", family="dense", n_layers=1, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64)


def test_rope_relative_property():
    """RoPE: q·k depends only on relative positions."""
    hd = 32
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 1, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, hd))
    def dot_at(pq, pk):
        qr = nn.apply_rope(q, jnp.array([[pq]]))
        kr = nn.apply_rope(k, jnp.array([[pk]]))
        return float(jnp.sum(qr * kr))
    assert dot_at(5, 3) == pytest.approx(dot_at(105, 103), rel=1e-4)
    assert dot_at(5, 3) != pytest.approx(dot_at(5, 4), rel=1e-3)


def test_mamba2_forward_matches_decode_steps():
    from repro.configs import get_config
    cfg = get_config("zamba2-1.2b", smoke=True)
    params = ssm.init_mamba2(jax.random.PRNGKey(0), cfg)
    b, s = 2, 64
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.d_model)) * 0.5
    y_par = ssm.mamba2_forward(params, x, cfg)
    state = ssm.init_mamba2_state(cfg, b)
    ys = []
    for i in range(s):
        y, state = ssm.mamba2_step(params, x[:, i], state, cfg)
        ys.append(y)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(y_par, y_seq, rtol=2e-3, atol=2e-3)


def test_mlstm_forward_matches_decode_steps():
    from repro.configs import get_config
    cfg = get_config("xlstm-350m", smoke=True)
    params = ssm.init_mlstm(jax.random.PRNGKey(0), cfg)
    b, s = 1, 64
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.d_model)) * 0.5
    y_par = ssm.mlstm_forward(params, x, cfg)
    state = ssm.init_mlstm_state(cfg, b)
    ys = []
    for i in range(s):
        y, state = ssm.mlstm_step(params, x[:, i], state, cfg)
        ys.append(y)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(y_par, y_seq, rtol=2e-3, atol=2e-3)


def test_slstm_forward_matches_decode_steps():
    from repro.configs import get_config
    cfg = get_config("xlstm-350m", smoke=True)
    params = ssm.init_slstm(jax.random.PRNGKey(0), cfg)
    b, s = 2, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.d_model)) * 0.5
    y_par = ssm.slstm_forward(params, x, cfg)
    carry = ssm.init_slstm_state(cfg, b)
    ys = []
    for i in range(s):
        y, carry = ssm.slstm_step(params, x[:, i], carry, cfg)
        ys.append(y)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(y_par, y_seq, rtol=2e-3, atol=2e-3)


def test_causal_conv_matches_steps():
    b, s, c, k = 2, 10, 6, 4
    x = jax.random.normal(jax.random.PRNGKey(0), (b, s, c))
    w = jax.random.normal(jax.random.PRNGKey(1), (k, c)) * 0.3
    bias = jnp.zeros((c,))
    y_par = ssm.causal_conv1d(x, w, bias)
    state = jnp.zeros((b, k - 1, c))
    ys = []
    for i in range(s):
        y, state = ssm.causal_conv1d_step(state, x[:, i], w, bias)
        ys.append(y)
    np.testing.assert_allclose(y_par, jnp.stack(ys, axis=1),
                               rtol=1e-5, atol=1e-5)
