"""Speculative decoding (``serving/backends.SpecDecodeBackend``).

The load-bearing property: greedy-exact acceptance makes spec decode
**token-identical** to target-only greedy decode — for ANY draft model
(zero-accept random drafts through full-accept self-drafts), on BOTH
inner backends, across staggered joins — with KV state rolled back past
the accept point (slot: per-lane index rewind; paged: lane lengths +
tail-block rewind with no leaked blocks and the ledger back at baseline).
"""

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import get_config
from repro.core.spilling import DeviceMemory
from repro.models import api
from repro.models.registry import spec as family_spec
from repro.serving import (CapabilityFallbackWarning, InferenceEngine,
                           SpecDecodeBackend)

MAX_SEQ = 48
CAPACITY = 4


@functools.lru_cache(maxsize=None)
def _dense():
    cfg = get_config("qwen3-0.6b", smoke=True)
    return cfg, api.init_params(cfg, jax.random.PRNGKey(0))


@functools.lru_cache(maxsize=None)
def _drafts():
    """Draft param sets: 'self' accepts every draft (greedy determinism),
    fresh random inits accept essentially none."""
    cfg, params = _dense()
    return {"self": params,
            7: api.init_params(cfg, jax.random.PRNGKey(7)),
            13: api.init_params(cfg, jax.random.PRNGKey(13))}


@pytest.fixture(scope="module")
def dense():
    return _dense()


@pytest.fixture(scope="module")
def drafts(dense):
    return _drafts()


def _workload(cfg, seed, n=4):
    rng = np.random.RandomState(seed)
    prompts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(100 + seed * 16 + i),
        (int(rng.randint(3, 12)),), 0, cfg.vocab_size, jnp.int32))
        for i in range(n)]
    gens = [int(rng.randint(2, 12)) for _ in range(n)]
    return prompts, gens


def _run(cfg, params, prompts, gens, **kw):
    eng = InferenceEngine(cfg, params, capacity=CAPACITY, max_seq=MAX_SEQ,
                          **kw)
    reqs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
    eng.run()
    return eng, [r.generated for r in reqs]


@functools.lru_cache(maxsize=None)
def _baseline_cache():
    return {}


def _baseline(seed):
    cache = _baseline_cache()
    if seed not in cache:
        cfg, params = _dense()
        prompts, gens = _workload(cfg, seed)
        _, toks = _run(cfg, params, prompts, gens)
        cache[seed] = toks
    return cache[seed]


# ---------------------------------------------------------------------------
# the property: token identity for random draft/target pairs, both inners
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(inner=st.sampled_from(["slot", "paged"]),
       draft=st.sampled_from(["self", 7, 13]),
       draft_k=st.sampled_from([1, 3]),
       seed=st.integers(min_value=0, max_value=2))
def test_spec_token_identical_to_plain_greedy(inner, draft, draft_k, seed):
    cfg, params = _dense()
    prompts, gens = _workload(cfg, seed)
    eng, toks = _run(cfg, params, prompts, gens, backend="spec",
                     spec_inner=inner, draft_cfg=cfg,
                     draft_params=_drafts()[draft], draft_k=draft_k,
                     block_size=4)
    assert toks == _baseline(seed), \
        f"spec({inner}, draft={draft}, k={draft_k}) diverged"
    s = eng.summary()
    # every verify forward yields between 1 and k tokens
    assert s["target_steps"] <= s["spec_tokens"] \
        <= s["target_steps"] * draft_k
    if inner == "paged":
        # rollback freed every speculative tail block; nothing leaked
        assert eng.backend.inner.pool.n_used == 0
        assert eng.backend.inner.ledger.kv_reserved_bytes == 0


def test_full_accept_rounds_save_target_steps(dense):
    """Self-draft = the full-accept extreme: every round accepts all k
    drafts, so target verify steps are strictly fewer than tokens."""
    cfg, params = dense
    prompts, gens = _workload(cfg, 3)
    for inner in ("slot", "paged"):
        eng, toks = _run(cfg, params, prompts, gens, backend="spec",
                         spec_inner=inner, draft_cfg=cfg,
                         draft_params=params, draft_k=4, block_size=4)
        assert toks == _baseline(3)
        s = eng.summary()
        assert s["draft_accept_rate"] == 1.0
        assert s["target_steps"] < s["spec_tokens"]
        assert s["accepted_tokens_per_target_step"] > 1


def test_zero_accept_rounds_still_exact(dense, drafts):
    """A random draft agrees with the target essentially never: every
    round falls back to the target's own correction token — one token per
    verify step, outputs still exact."""
    cfg, params = dense
    prompts, gens = _workload(cfg, 1)
    eng, toks = _run(cfg, params, prompts, gens, backend="spec",
                     spec_inner="paged", draft_cfg=cfg,
                     draft_params=drafts[13], draft_k=3, block_size=4)
    assert toks == _baseline(1)
    s = eng.summary()
    assert s["draft_accept_rate"] < 1.0
    # zero-accept rounds emit exactly one (correction) token each
    assert s["spec_tokens"] >= s["target_steps"]


def test_paged_verify_headroom_at_max_seq(dense):
    """A request whose decode extent exactly fills max_seq: the k verify
    rows land past it, in the reservation's headroom — allocation must
    never fail and the tail blocks must rewind."""
    cfg, params = dense
    plen = 8
    gen = MAX_SEQ - plen + 1        # prompt + gen - 1 == MAX_SEQ
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(5), (plen,), 0, cfg.vocab_size, jnp.int32))
    _, base = _run(cfg, params, [prompt], [gen])
    eng, toks = _run(cfg, params, [prompt], [gen], backend="spec",
                     spec_inner="paged", draft_cfg=cfg,
                     draft_params=params, draft_k=4, block_size=4)
    assert toks == base
    assert eng.backend.inner.pool.n_used == 0
    assert eng.backend.inner.ledger.kv_reserved_bytes == 0


def test_staggered_joins_do_not_perturb_spec_rounds(dense, drafts):
    """Requests joining mid-flight enter rounds whose other lanes hold
    buffered tokens; the masked-lane machinery must keep everyone exact."""
    cfg, params = dense
    prompts, gens = _workload(cfg, 2, n=6)
    base = []
    for p, g in zip(prompts, gens):
        _, t = _run(cfg, params, [p], [g])
        base.append(t[0])
    eng = InferenceEngine(cfg, params, capacity=3, max_seq=MAX_SEQ,
                          backend="spec", spec_inner="paged", draft_cfg=cfg,
                          draft_params=drafts[7], draft_k=3, block_size=4)
    reqs = [eng.submit(prompts[0], gens[0]), eng.submit(prompts[1], gens[1])]
    n = 2
    while eng.has_work() or n < len(prompts):
        if n < len(prompts):
            reqs.append(eng.submit(prompts[n], gens[n]))
            n += 1
        eng.step()
    eng.run()
    assert [r.generated for r in reqs] == base


def test_eos_mid_buffer_stops_early_and_exact(dense):
    cfg, params = dense
    prompts, gens = _workload(cfg, 0)
    base = _baseline(0)[0]
    eos = base[1]                   # stop at this token's first occurrence
    eng = InferenceEngine(cfg, params, capacity=CAPACITY, max_seq=MAX_SEQ,
                          backend="spec", draft_cfg=cfg, draft_params=params,
                          draft_k=4)
    req = eng.submit(prompts[0], gens[0], eos_id=eos)
    eng.run()
    assert req.generated == base[:base.index(eos) + 1]


# ---------------------------------------------------------------------------
# ledger accounting: draft + target + headroom on ONE shared budget
# ---------------------------------------------------------------------------

def test_shared_ledger_charges_draft_and_target(dense):
    cfg, params = dense
    ledger = DeviceMemory(0, 64 * 2**20)
    eng = InferenceEngine(cfg, params, capacity=2, max_seq=MAX_SEQ,
                          backend="spec", spec_inner="paged", draft_cfg=cfg,
                          draft_params=params, draft_k=2, block_size=4,
                          ledger=ledger)
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (6,), 0, cfg.vocab_size, jnp.int32))
    req = eng.submit(prompt, 4)
    eng.step()
    draft_bytes = eng.backend.draft_slot_bytes
    # mid-flight: the ledger holds the draft state AND the target blocks
    assert ledger.kv_reserved_bytes >= draft_bytes \
        + req.reserved_blocks * eng.backend.inner.pool.block_bytes
    eng.run()
    assert ledger.kv_reserved_bytes == 0


def test_private_paged_budget_charges_draft_state(dense):
    """Without a shared session ledger, the draft state still reserves
    against the paged inner's private ledger — a user sizing
    kv_budget_bytes bounds draft + target together."""
    cfg, params = dense
    eng = InferenceEngine(cfg, params, capacity=2, max_seq=MAX_SEQ,
                          backend="spec", spec_inner="paged", draft_cfg=cfg,
                          draft_params=params, draft_k=2, block_size=4,
                          kv_budget_bytes=8 * 2**20)
    ledger = eng.backend.inner.ledger
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(4), (6,), 0, cfg.vocab_size, jnp.int32))
    req = eng.submit(prompt, 4)
    eng.step()
    assert ledger.kv_reserved_bytes >= eng.backend.draft_slot_bytes \
        + req.reserved_blocks * eng.backend.inner.pool.block_bytes
    eng.run()
    assert ledger.kv_reserved_bytes == 0


def test_never_admissible_spec_request_rejected_at_submit(dense):
    cfg, params = dense
    spec = family_spec(cfg)
    # fits ONE target slot (incl. headroom) but not target + draft state:
    # the spec-level combined admission check must reject up front
    slot_bytes = spec.decode_state_bytes(cfg, 1, MAX_SEQ + 2)
    tight = DeviceMemory(0, slot_bytes + 1)
    eng = InferenceEngine(cfg, params, capacity=2, max_seq=MAX_SEQ,
                          backend="spec", draft_cfg=cfg, draft_params=params,
                          draft_k=2, ledger=tight)
    prompt = np.asarray([1, 2, 3], np.int32)
    with pytest.raises(ValueError, match="never admit"):
        eng.submit(prompt, 4)


# ---------------------------------------------------------------------------
# capability gates + construction validation
# ---------------------------------------------------------------------------

def test_spec_falls_back_on_undraftable_family():
    cfg = get_config("xlstm-350m", smoke=True)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    dense_cfg = get_config("qwen3-0.6b", smoke=True)
    with pytest.warns(CapabilityFallbackWarning, match="spec_draftable"):
        eng = InferenceEngine(cfg, params, capacity=2, max_seq=32,
                              backend="spec", draft_cfg=dense_cfg,
                              draft_params=None)
    assert eng.backend.name == "slot"
    assert eng.requested_backend == "spec"


def test_spec_backend_validates_draft(dense):
    cfg, params = dense
    with pytest.raises(ValueError, match="draft member model"):
        SpecDecodeBackend(cfg, 2, 32)
    ssm_cfg = get_config("xlstm-350m", smoke=True)
    with pytest.raises(ValueError, match="rolled back"):
        SpecDecodeBackend(cfg, 2, 32, draft_cfg=ssm_cfg, draft_params={})
    with pytest.raises(ValueError, match="draft_k"):
        SpecDecodeBackend(cfg, 2, 32, draft_cfg=cfg, draft_params=params,
                          draft_k=0)


def test_verify_step_gated_on_capability():
    ssm_cfg = get_config("xlstm-350m", smoke=True)
    with pytest.raises(ValueError, match="spec_draftable|rolled back"):
        api.verify_step(ssm_cfg, {}, {}, np.zeros((1, 2), np.int32))
    assert "spec_draftable" in family_spec(ssm_cfg).capabilities()
    assert family_spec("dense").spec_draftable


# ---------------------------------------------------------------------------
# session surface
# ---------------------------------------------------------------------------

def test_session_spec_job_end_to_end(dense):
    from repro.api import HydraConfig, ServeJob, Session
    cfg, params = dense
    session = Session(HydraConfig(n_devices=1,
                                  device_budget_bytes=96 * 2**20))
    jid = session.submit(ServeJob(cfg, params=params, backend="spec",
                                  draft_model=cfg, draft_params=params,
                                  draft_k=3, spec_inner="paged",
                                  capacity=3, max_seq=MAX_SEQ,
                                  block_size=4))
    plan = session.plan()
    meta = plan.job(jid).meta
    assert meta["backend"] == "spec"
    assert meta["spec_inner"] == "paged"
    assert meta["draft_model"] == cfg.name
    assert meta["draft_k"] == 3
    assert meta["draft_state_bytes"] > 0 and meta["shared_ledger"]
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(3), (6,), 0, cfg.vocab_size, jnp.int32))
    session.submit_request(jid, prompt, 5)
    assert session.poll(jid)["backend"] == "spec"
    assert session.poll(jid)["capabilities"]["spec_draftable"]
    report = session.run(plan)
    rec = report.serve[jid]
    assert rec["backend"] == "spec" and rec["inner_backend"] == "paged"
    assert rec["n_completed"] == 1
    assert rec["accepted_tokens_per_target_step"] >= 1
    # the session ledger settled once the request retired
    assert session.devices[0].kv_reserved_bytes == 0


# ---------------------------------------------------------------------------
# fused multi-query paged-verify kernel (kernels/paged_attention.py)
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(draft=st.sampled_from(["self", 7, 13]),
       draft_k=st.sampled_from([1, 3]),
       seed=st.integers(min_value=0, max_value=2))
def test_fused_verify_token_identical_to_gathered(draft, draft_k, seed):
    """Property: the fused multi-query verify kernel (all k draft rows
    scored through block tables in ONE launch) is token-identical to the
    gathered-jnp verify path — across accept-rate extremes and k."""
    cfg, params = _dense()
    prompts, gens = _workload(cfg, seed)
    _, toks = _run(cfg, params, prompts, gens, backend="spec",
                   spec_inner="paged", draft_cfg=cfg,
                   draft_params=_drafts()[draft], draft_k=draft_k,
                   block_size=4, verify_impl="pallas_interpret")
    assert toks == _baseline(seed), \
        f"fused verify(draft={draft}, k={draft_k}) diverged from greedy"


def test_fused_verify_staggered_joins(dense, drafts):
    """Mid-flight joins under the fused verify kernel: fresh lanes enter
    rounds through the same batched launch as buffered lanes."""
    cfg, params = dense
    prompts, gens = _workload(cfg, 4, n=5)
    base = []
    for p, g in zip(prompts, gens):
        _, t = _run(cfg, params, [p], [g])
        base.append(t[0])
    eng = InferenceEngine(cfg, params, capacity=2, max_seq=MAX_SEQ,
                          backend="spec", spec_inner="paged", draft_cfg=cfg,
                          draft_params=drafts[7], draft_k=3, block_size=4,
                          verify_impl="pallas_interpret")
    reqs = [eng.submit(prompts[0], gens[0])]
    n = 1
    while eng.has_work() or n < len(prompts):
        if n < len(prompts):
            reqs.append(eng.submit(prompts[n], gens[n]))
            n += 1
        eng.step()
    eng.run()
    assert [r.generated for r in reqs] == base
    assert eng.backend.verify_impl == "pallas_interpret"
    assert eng.backend.inner.pool.n_used == 0


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10**6), kk=st.sampled_from([1, 3]))
def test_fused_verify_on_preemption_shaped_tables(seed, kk):
    """Preempt/resume leaves lanes with interleaved, non-monotone block
    tables (resumed snapshots re-attach wherever free blocks landed) and
    aliased prefix blocks (COW sharing).  The kernel must match the
    gathered oracle on exactly that table-state space: scrambled physical
    order, shared blocks across lanes, rewound lengths, garbage tails."""
    from repro.kernels import ops, ref
    n, nkv, groups, hd, bs, B = 3, 2, 2, 32, 4, 4
    rng = np.random.default_rng(seed)
    P = n * B + 2
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    kp = jax.random.normal(k1, (P, bs, nkv, hd), jnp.float32)
    vp = jax.random.normal(k2, (P, bs, nkv, hd), jnp.float32)
    q = jax.random.normal(k3, (n, kk, nkv * groups, hd), jnp.float32)
    # scrambled physical order per lane (resume re-attach)
    tables = (rng.permutation(P - 1)[: n * B] + 1).reshape(n, B)
    # lanes 1 and 2 alias lane 0's first block (shared prompt prefix)
    tables[1, 0] = tables[2, 0] = tables[0, 0]
    # lane 2's tail points at the garbage block (short, rewound lane)
    tables[2, 2:] = 0
    tables = jnp.asarray(tables, jnp.int32)
    # rewound lengths: mid-block accept points, one lane at a boundary
    lengths = jnp.asarray(
        [int(rng.integers(0, B * bs - kk + 1)), bs, 2], jnp.int32)
    out = ops.paged_verify(q, kp, vp, tables, lengths,
                           impl="pallas_interpret")
    exp = ref.paged_verify_ref(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


def test_verify_impl_rejected_on_slot_inner(dense):
    cfg, params = dense
    with pytest.raises(ValueError, match="verify_impl"):
        SpecDecodeBackend(cfg, 2, 32, draft_cfg=cfg, draft_params=params,
                          inner="slot", verify_impl="pallas")
    from repro.api import ServeJob
    with pytest.raises(ValueError, match="verify_impl"):
        ServeJob(cfg, backend="paged",
                 verify_impl="pallas").validate_tiering()


def test_serve_job_spec_validation(dense):
    from repro.api import ServeJob
    cfg, _ = dense
    with pytest.raises(ValueError, match="draft member model"):
        ServeJob(cfg, backend="spec").requested_backend()
    # a bad DRAFT has no fallback: it must fail at submit/plan time, not
    # mid-run in the backend constructor
    ssm_cfg = get_config("xlstm-350m", smoke=True)
    with pytest.raises(ValueError, match="spec_draftable|rolled back"):
        ServeJob(cfg, backend="spec",
                 draft_model=ssm_cfg).requested_backend()
    with pytest.raises(ValueError, match="spec_inner"):
        ServeJob(cfg, backend="spec", draft_model=cfg,
                 spec_inner="bogus").resolved_spec_inner()
    job = ServeJob(cfg, backend="spec", draft_model=cfg, spec_inner="paged")
    assert job.effective_backend() == "spec"
    assert job.effective_spec_inner() == "paged"
    ssm = get_config("xlstm-350m", smoke=True)
    job = ServeJob(ssm, backend="spec", draft_model=cfg)
    assert job.effective_backend() == "slot"
    assert job.effective_spec_inner() is None
