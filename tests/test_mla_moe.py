"""Moonlight-class blocks (latent attention, dropless held-expert MoE with
shared experts, a leading dense layer, an untied head) against the plain
f32 reference kept with the benchmark (bench/configs/moonlight-16b-a3b.py),
at the smoke size of the same structure: 1 dense + 2 MoE layers, 8 routed
experts of which 4 are held, top-2, MLA dims scaled, vocab 512.

Tolerances: the program runs in f32 here, so program and reference compute
the same math in the same precision and differ only by the order of f32
sums (about 1e-6 relative); each bound leaves two orders of magnitude
above that and stays far below any change of the mathematics."""

from __future__ import annotations

import dataclasses
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.moonlight_16b_a3b import CUT_CONFIG
from repro.models import api, mla_moe
from repro.models import layers as nn

BENCH = Path(__file__).resolve().parents[1] / "bench"
CFG = get_config("moonlight-16b-a3b", smoke=True).replace(
    dtype=jnp.float32)
TOL = 1e-4          # f32 on both sides: summation order only


@pytest.fixture(scope="module")
def ref():
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    spec = importlib.util.spec_from_file_location(
        "moonlight_reference", BENCH / "configs" / "moonlight-16b-a3b.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _m(cfg) -> dict:
    return dataclasses.asdict(cfg)


def _ref_params(params) -> dict:
    n_moe = params["layers"]["attn_norm"]["scale"].shape[0]
    return {"embed": params["embed"], "head": params["head"],
            "blocks": [jax.tree.map(lambda a: a[0], params["dense"])]
            + [jax.tree.map(lambda a, i=i: a[i], params["layers"])
               for i in range(n_moe)]}


def _tokens(b=2, s=32, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, s + 1), 0,
                              CFG.vocab_size, jnp.int32)


def test_forward_logits_match_reference(ref):
    params = api.init_params(CFG, jax.random.PRNGKey(0))
    toks = _tokens()[:, :-1]
    got = jax.jit(lambda p, t: api.forward(CFG, p, {"tokens": t}))(params,
                                                                    toks)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, t: ref.forward_logits(_m(CFG), p, t))(
            _ref_params(params), toks)
    np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)


def test_sharp_step_loss_and_every_gradient_match_reference(ref):
    """One SHARP step through Session -> TrainJob, spilled in several
    shards: the loss, and each leaf's gradient as the optimizer got it
    (the Adam first moment after one step is (1 - b1) x the gradient)."""
    import hydra
    params = api.init_params(CFG, jax.random.PRNGKey(3))
    toks = _tokens(seed=4)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    session = hydra.Session(hydra.HydraConfig(
        n_devices=1, device_budget_bytes=3 * 2**20, pilot=False),
        profile=None)
    tj = hydra.TrainJob(CFG, dataloader=iter(lambda: batch, None), lr=1e-3,
                        epochs=1, steps_per_epoch=1, batch=2, seq=32,
                        optimizer="adamw", params=params)
    session.submit(tj)
    (ex,) = session.train_execs
    assert len(ex.partition.shards) >= 2
    session.run()
    b1 = tj.opt_config().b1
    got = {}
    for shard in ex.partition.shards:
        mu = ex.store.opt[shard.index]["mu"]
        for k, i in enumerate(range(shard.seg_lo, shard.seg_hi)):
            got[ex.plan.segments[i].param_ref] = jax.tree.map(
                lambda a: np.asarray(a) / (1 - b1), mu[k])

    m = _m(CFG)
    with jax.default_matmul_precision("highest"):
        loss, g = jax.jit(jax.value_and_grad(
            lambda p: ref.forward_loss(m, p, batch["tokens"],
                                       batch["labels"])))(_ref_params(params))
    assert abs(ex.losses[0] - float(loss)) < TOL
    want = {("embed",): g["embed"], ("head",): g["head"],
            ("stack_slice", "dense", 0, 1): g["blocks"][0],
            ("stack_slice", "layers", 0, 1): g["blocks"][1],
            ("stack_slice", "layers", 1, 2): g["blocks"][2]}
    for r, tree in want.items():
        mine = got[r]
        if r[0] == "stack_slice":
            mine = jax.tree.map(lambda a: a[0], mine)
        assert jax.tree.structure(mine) == jax.tree.structure(tree)
        for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(tree)):
            scale = max(float(jnp.max(jnp.abs(b))), 1e-6)
            np.testing.assert_allclose(a / scale, b / scale, atol=1e-3)
    # the selection bias is a buffer: no gradient
    assert not np.any(got[("stack_slice", "layers", 0, 1)]["moe"]["bias"])


def _moe_input(T=64, seed=7):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, T, CFG.d_model))


def test_expert_shares_sum_to_the_uncut_layer(monkeypatch):
    """Two disjoint held sets, with the shared experts counted once, add
    up to the layer that holds all 8 experts; in one routed pass or in
    several (the token chunks of a long batch)."""
    key = jax.random.PRNGKey(11)
    whole = CFG.replace(n_experts_held=0)
    a, b = CFG.replace(expert_offset=0), CFG.replace(expert_offset=4)
    pw, pa, pb = (mla_moe.init_moe(key, c) for c in (whole, a, b))
    for k in ("w_gate", "w_up", "w_down"):
        np.testing.assert_array_equal(
            pw[k], jnp.concatenate([pa[k], pb[k]]))
    x = _moe_input()
    yw, cw = mla_moe.moe(pw, x, whole)
    ya, ca = mla_moe.moe(pa, x, a)
    yb, cb = mla_moe.moe(pb, x, b)
    shared = nn.swiglu(pw["shared"], x)
    np.testing.assert_allclose(ya + yb - shared, yw, rtol=TOL, atol=TOL)
    # every (token, slot) pair is computed once, by the share that holds
    # its expert; whole row tiles of the grouped matmul count as computed
    assert int(jnp.sum(ca["expert_rows"]) + jnp.sum(cb["expert_rows"])) \
        >= 64 * CFG.top_k
    monkeypatch.setattr(mla_moe, "MOE_TOKEN_CHUNK", 16)
    yc, cc = mla_moe.moe(pa, x, a)
    assert int(cc["expert_passes"]) == 4
    np.testing.assert_allclose(yc, ya, rtol=TOL, atol=TOL)


def test_held_rows_sort_and_row_moves_match_plain_gather_and_scatter():
    """The counting sort orders pairs as a stable argsort by held expert,
    and the token <-> buffer row moves (gathers both ways) give the values
    and gradients of the plain gather and scatter-add they replace."""
    cfg = CFG.replace(n_experts=8, n_experts_held=4, expert_offset=2,
                      top_k=3)
    T, K, d = 40, cfg.top_k, 16
    idx = jax.random.randint(jax.random.PRNGKey(3), (T, K), 0, 8)
    tok, pair, sizes, M, dest, held = mla_moe._held_rows(cfg, idx)
    local = idx.reshape(-1) - 2
    key = jnp.where((local >= 0) & (local < 4), local, 4)
    order = jnp.argsort(key, stable=True)[:M]
    np.testing.assert_array_equal(pair, order)
    np.testing.assert_array_equal(sizes, jnp.bincount(key, length=5)[:4])
    n = int(jnp.sum(sizes))
    valid = (jnp.arange(M) < n)[:, None]
    x = jax.random.normal(jax.random.PRNGKey(4), (T, d))
    rows = jax.random.normal(jax.random.PRNGKey(5), (M, d))

    def moved(x, rows):          # rows past the groups carry nothing
        xs = jnp.where(valid, mla_moe._dispatch(x, tok, dest, held), 0)
        return xs, mla_moe._combine(rows * valid, tok, dest, held)

    def plain(x, rows):
        return (jnp.where(valid, x[tok], 0),
                jnp.zeros((T, d)).at[tok].add(rows * valid))
    got, want = moved(x, rows), plain(x, rows)
    cot = (jax.random.normal(jax.random.PRNGKey(6), (M, d)),
           jax.random.normal(jax.random.PRNGKey(7), (T, d)))
    g_got = jax.vjp(moved, x, rows)[1](cot)
    g_want = jax.vjp(plain, x, rows)[1](cot)
    for a, b in zip((*got, *g_got), (*want, *g_want)):
        np.testing.assert_allclose(a, b, rtol=TOL, atol=TOL)


def test_rows_computed_counts_whole_tiles():
    """Groups of 3, 0, 5 and 9 rows in a buffer of 32 rows with 8-row
    tiles: rows 0-2 take tile 0, rows 3-7 tile 0, rows 8-16 tiles 1-2."""
    mla_moe.GMM_TILING, old = (8,) + mla_moe.GMM_TILING[1:], mla_moe.GMM_TILING
    try:
        got = mla_moe.rows_computed(jnp.array([3, 0, 5, 9]), 32)
    finally:
        mla_moe.GMM_TILING = old
    np.testing.assert_array_equal(got, [8, 0, 8, 16])


def test_dropless_every_token_on_one_expert(ref):
    """Every token routed to held expert 3 (and to expert 6, held
    elsewhere): all of them are computed, none dropped, and the layer
    equals the reference's dense sum over tokens."""
    p = mla_moe.init_moe(jax.random.PRNGKey(12), CFG)
    p["bias"] = jnp.zeros((CFG.n_experts,)).at[3].set(10.0).at[6].set(9.0)
    x = _moe_input()
    y, counts = mla_moe.moe(p, x, CFG)
    rows = np.asarray(counts["expert_rows"])
    assert list(rows[:3]) == [0, 0, 0] and rows[3] >= 64
    with jax.default_matmul_precision("highest"):
        want = ref.moe(_m(CFG), p, x, "f32")
    np.testing.assert_allclose(y, want, rtol=TOL, atol=TOL)


def test_selection_bias_chooses_and_unbiased_scores_weigh():
    p = mla_moe.init_moe(jax.random.PRNGKey(13), CFG)
    p["bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(14),
                                        (CFG.n_experts,))
    x = _moe_input()[0]
    idx, w = mla_moe.route(p, x, CFG)
    scores = jax.nn.sigmoid(jnp.matmul(x, p["router"],
                                       precision="highest"))
    _, want_idx = jax.lax.top_k(scores + p["bias"], CFG.top_k)
    _, unbiased_idx = jax.lax.top_k(scores, CFG.top_k)
    np.testing.assert_array_equal(idx, want_idx)
    assert np.any(np.asarray(idx) != np.asarray(unbiased_idx))
    s = jnp.take_along_axis(scores, idx, -1)
    np.testing.assert_allclose(
        w, s / jnp.sum(s, -1, keepdims=True) * CFG.routed_scaling_factor,
        rtol=1e-6)


def test_cut_config_holds_1_07b_parameters(ref):
    for cfg in (CUT_CONFIG, CFG):
        shapes = jax.eval_shape(lambda c=cfg: api.init_params(
            c, jax.random.PRNGKey(0)))
        assert cfg.n_params == api.param_count(shapes)
        assert cfg.n_params == ref.n_params(_m(cfg))
    assert abs(CUT_CONFIG.n_params / 1.07e9 - 1) < 0.01
    # the published model: about 16 B parameters, 3 B active
    full = get_config("moonlight-16b-a3b")
    assert abs(full.n_params / 16e9 - 1) < 0.01
    assert abs(full.n_active_params / 3e9 - 1) < 0.05


def test_decode_through_the_latent_cache_matches_forward():
    params = api.init_params(CFG, jax.random.PRNGKey(5))
    toks = _tokens(s=12, seed=6)[:, :-1]
    full = api.forward(CFG, params, {"tokens": toks})
    state = api.init_decode_state(CFG, 2, 16)
    step = jax.jit(lambda s, t: api.decode_step(CFG, params, s, t))
    for i in range(toks.shape[1]):
        logits, state = step(state, toks[:, i:i + 1])
        np.testing.assert_allclose(logits[:, 0], full[:, i], rtol=TOL,
                                   atol=TOL)


@pytest.mark.parametrize("sq,skv,causal,window,groups,offset", [
    (40, 40, True, None, 1, 0), (48, 48, False, None, 2, 0),
    (32, 45, True, 7, 1, 13)])
def test_blockwise_attention_matches_dense(sq, skv, causal, window, groups,
                                           offset):
    """Forward and all three gradients, v narrower than q and k, padded
    blocks, skipped blocks."""
    b, nkv, hd, hv = 2, 2, 24, 16
    ks = jax.random.split(jax.random.PRNGKey(sq + skv), 4)
    q = jax.random.normal(ks[0], (b, sq, nkv, groups, hd))
    k = jax.random.normal(ks[1], (b, skv, nkv, hd))
    v = jax.random.normal(ks[2], (b, skv, nkv, hv))
    qpos, kpos = jnp.arange(sq) + offset, jnp.arange(skv)
    scale = hd ** -0.5

    def dense(q, k, v):
        return nn._sdpa_dense(q, k, v, scale, qpos, kpos, causal, window)

    def blockwise(q, k, v):
        return nn.blockwise_attention(q, k, v, scale, qpos, kpos, causal,
                                      window, block=16)
    ct = jax.random.normal(ks[3], (b, sq, nkv, groups, hv))
    np.testing.assert_allclose(blockwise(q, k, v), dense(q, k, v),
                               rtol=TOL, atol=TOL)
    gd = jax.grad(lambda *a: jnp.sum(dense(*a) * ct), (0, 1, 2))(q, k, v)
    gb = jax.grad(lambda *a: jnp.sum(blockwise(*a) * ct), (0, 1, 2))(q, k, v)
    for x, y in zip(gb, gd):
        np.testing.assert_allclose(x, y, rtol=TOL, atol=TOL)
