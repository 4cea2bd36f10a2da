"""FamilySpec registry contract: every registered family's *declared*
capabilities must match *behavior*.

The registry (repro.models.registry) is the single source of capability
truth for the serving backends, prefill factories, and the session
planner — a spec that over- or under-declares would silently break
admission sizing or token identity, so this suite checks each flag
against the real code path:

* ``batched_prefill``: consuming a whole prompt chunk in ONE decode_step
  call is token-identical to the per-token loop iff declared;
* ``padded_prefill``: the padded-prefill factory builds (and is
  token-identical) iff declared;
* ``paging``: the paged decode path exists iff declared (and the paged
  engine is token-identical to the slot engine — tests/test_serving.py);
* ``servable``: the engine accepts the family iff declared;
* cost fns: ``decode_state_bytes`` / ``kv_block_bytes`` equal the
  ``jax.eval_shape``-derived byte totals of the real constructors.
"""

import math
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api
from repro.models import registry

FAMILY_ARCH = {
    "dense": "qwen3-0.6b",
    "vlm": "llava-next-mistral-7b",
    "moe": "mixtral-8x22b",
    "mla_moe": "moonlight-16b-a3b",
    "ssm": "xlstm-350m",
    "hybrid": "zamba2-1.2b",
    "audio": "whisper-medium",
}

MAX_SEQ = 32


def _cfg(family):
    return get_config(FAMILY_ARCH[family], smoke=True)


def test_every_family_is_registered():
    assert set(registry.registered_families()) == set(FAMILY_ARCH)


@pytest.mark.parametrize("family", sorted(FAMILY_ARCH))
def test_spec_module_implements_the_family_surface(family):
    spec = registry.spec(family)
    for fn in ("init_params", "forward", "init_decode_state", "decode_step"):
        assert hasattr(spec.module, fn), f"{family}: module lacks {fn}"
    if spec.paging:
        assert hasattr(spec.module, "paged_decode_step")


@pytest.mark.parametrize("family", sorted(FAMILY_ARCH))
def test_decode_state_cost_matches_eval_shape(family):
    cfg = _cfg(family)
    spec = registry.spec(cfg)
    shapes = jax.eval_shape(
        lambda: spec.module.init_decode_state(cfg, 1, MAX_SEQ))
    expect = sum(math.prod(x.shape) * x.dtype.itemsize
                 for x in jax.tree.leaves(shapes))
    assert spec.decode_state_bytes(cfg, 1, MAX_SEQ) == expect


@pytest.mark.parametrize("family", sorted(f for f in FAMILY_ARCH
                                          if registry.spec(f).paging))
def test_kv_block_cost_matches_eval_shape(family):
    cfg = _cfg(family)
    spec = registry.spec(cfg)
    shapes = jax.eval_shape(lambda: api.init_kv_pages(cfg, 1, 8))
    expect = sum(math.prod(x.shape) * x.dtype.itemsize
                 for x in jax.tree.leaves(shapes))
    assert spec.kv_block_bytes(cfg, 8) == expect


@pytest.mark.parametrize("family", ["dense", "moe", "ssm", "hybrid"])
def test_batched_prefill_declaration_matches_behavior(family):
    """Declared batched_prefill => one whole-chunk decode_step call equals
    the per-token loop exactly (argmax-identical last logits and the same
    write index).  Undeclared families still prefill correctly through the
    scan fallback — the factory must route on the declaration."""
    cfg = _cfg(family)
    spec = registry.spec(cfg)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 8), 0,
                                cfg.vocab_size, jnp.int32)

    state = api.init_decode_state(cfg, 2, MAX_SEQ)
    logits_l = None
    for i in range(tokens.shape[1]):
        logits_l, state = api.decode_step(cfg, params, state,
                                          tokens[:, i:i + 1])

    if spec.batched_prefill:
        state_b = api.init_decode_state(cfg, 2, MAX_SEQ)
        logits_b, _ = api.decode_step(cfg, params, state_b, tokens)
        assert (jnp.argmax(logits_b[:, -1], -1)
                == jnp.argmax(logits_l[:, -1], -1)).all()

    from repro.training.train_loop import make_prefill_into_cache
    state_f = api.init_decode_state(cfg, 2, MAX_SEQ)
    logits_f, _ = make_prefill_into_cache(cfg)(params, state_f, tokens)
    assert (jnp.argmax(logits_f, -1) == jnp.argmax(logits_l[:, -1], -1)).all()


@pytest.mark.parametrize("family", ["dense", "moe", "ssm", "hybrid"])
def test_padded_prefill_declaration_matches_behavior(family):
    """Declared padded_prefill => a right-padded prompt prefills
    argmax-identically to the exact-length one; undeclared => the factory
    refuses (silent wrong answers are the failure mode it guards)."""
    cfg = _cfg(family)
    spec = registry.spec(cfg)
    from repro.training.train_loop import (make_padded_prefill_into_cache,
                                           make_prefill_into_cache)
    if not spec.padded_prefill:
        with pytest.raises(ValueError, match="padded prefill"):
            make_padded_prefill_into_cache(cfg)
        return
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    plen, bucket = 6, 8
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, plen), 0,
                                cfg.vocab_size, jnp.int32)
    padded = jnp.pad(tokens, ((0, 0), (0, bucket - plen)))
    state = api.init_decode_state(cfg, 1, MAX_SEQ)
    exact, state_e = make_prefill_into_cache(cfg)(params, state, tokens)
    state = api.init_decode_state(cfg, 1, MAX_SEQ)
    pad, state_p = make_padded_prefill_into_cache(cfg)(
        params, state, padded, jnp.int32(plen))
    assert (jnp.argmax(exact, -1) == jnp.argmax(pad, -1)).all()
    assert int(state_p["kv"]["index"]) == int(state_e["kv"]["index"]) == plen


@pytest.mark.parametrize("family", ["moe", "ssm", "hybrid"])
def test_paging_undeclared_raises(family):
    cfg = _cfg(family)
    assert not registry.spec(cfg).paging
    with pytest.raises(ValueError):
        api.paged_decode_step(cfg, None, None, None, None, None)


def test_paging_declared_round_trips():
    """Declared paging => the paged decode step exists and one step through
    block tables is argmax-identical to the contiguous decode step."""
    cfg = _cfg("dense")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    plen, bs = 7, 4
    tokens = jax.random.randint(jax.random.PRNGKey(9), (1, plen), 0,
                                cfg.vocab_size, jnp.int32)
    state = api.init_decode_state(cfg, 1, MAX_SEQ)
    _, state = api.decode_step(cfg, params, state, tokens)
    nxt = jnp.asarray([[11]], jnp.int32)
    ref, _ = api.decode_step(cfg, params, state, nxt)

    # copy the contiguous cache into pages (blocks 1..) and decode via table
    pages = api.init_kv_pages(cfg, 4, bs)
    k, v = state["kv"]["k"], state["kv"]["v"]          # (L, 1, S, kv, hd)
    nb = -(-plen // bs)
    for j in range(nb):
        rows = k[:, 0, j * bs:(j + 1) * bs]
        pad = bs - rows.shape[1]
        if pad:
            rows = jnp.pad(rows, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pages["k"] = pages["k"].at[:, 1 + j].set(rows.astype(pages["k"].dtype))
        rows = v[:, 0, j * bs:(j + 1) * bs]
        if pad:
            rows = jnp.pad(rows, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pages["v"] = pages["v"].at[:, 1 + j].set(rows.astype(pages["v"].dtype))
    tables = jnp.zeros((1, 8), jnp.int32).at[0, :nb].set(
        jnp.arange(1, nb + 1))
    logits, _ = api.paged_decode_step(
        cfg, params, pages, tables, jnp.asarray([plen], jnp.int32), nxt)
    assert (jnp.argmax(logits[:, -1], -1) == jnp.argmax(ref[:, -1], -1)).all()


def test_servable_declaration_matches_engine():
    from repro.serving import InferenceEngine
    cfg = _cfg("audio")
    assert not registry.spec(cfg).servable
    with pytest.raises(ValueError, match="encoder-decoder"):
        InferenceEngine(cfg, params=None, capacity=1, max_seq=16)


def test_spec_lookup_by_cfg_and_name_and_unknown():
    cfg = _cfg("dense")
    assert registry.spec(cfg) is registry.spec("dense")
    with pytest.raises(KeyError, match="no registered model family"):
        registry.spec("not-a-family")


def test_families_with_capability_queries():
    assert set(registry.families_with("paging")) == {"dense", "vlm"}
    assert set(registry.families_with("batched_prefill")) \
        == {"dense", "vlm", "moe"}
    assert set(registry.families_with("padded_prefill")) == {"dense", "vlm"}
    assert "audio" not in registry.families_with("servable")


def test_every_absent_capability_has_a_reason():
    for family in registry.registered_families():
        spec = registry.spec(family)
        for cap, on in spec.capabilities().items():
            if not on:
                assert spec.why_not(cap) != \
                    "not declared by the family spec", \
                    f"{family}.{cap}: absent capability needs a note"


# ---------------------------------------------------------------------------
# deprecated predicate shims (one release of grace, then delete)
# ---------------------------------------------------------------------------

def test_deprecated_predicates_still_answer_through_the_registry():
    dense, moe = _cfg("dense"), _cfg("moe")
    with pytest.warns(DeprecationWarning):
        assert api.is_attention_family(dense)
    with pytest.warns(DeprecationWarning):
        assert not api.supports_padded_prefill(moe)
    with pytest.warns(DeprecationWarning):
        assert api.supports_paging(dense) and not api.supports_paging(moe)
    with pytest.warns(DeprecationWarning):
        assert set(api.ATTENTION_FAMILIES) == {"dense", "vlm", "moe"}
    with pytest.warns(DeprecationWarning):
        assert set(api.PAGED_FAMILIES) == {"dense", "vlm"}
    with pytest.raises(AttributeError):
        api.NOT_A_THING


def test_registry_symbols_reexported_from_hydra():
    import hydra
    assert hydra.family_spec(_cfg("dense")).paging
    assert isinstance(hydra.family_spec("ssm"), hydra.FamilySpec)
    assert "dense" in hydra.registered_families()
    assert issubclass(hydra.CapabilityFallbackWarning, UserWarning)
    assert isinstance(hydra.SlotBackend, type)
    assert isinstance(hydra.PagedBackend, type)
