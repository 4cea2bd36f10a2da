"""Model spilling (paper §4.2): promote/demote roundtrips, budget
enforcement, shared-grad accumulation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import partitioner as pt
from repro.core import shard_graph as sg
from repro.core.spilling import DeviceMemory, HostModelStore
from repro.models import api
from repro.optim import OptimizerConfig


def _store(arch="qwen3-0.6b", budget=20 * 10**6):
    cfg = get_config(arch, smoke=True)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    plan = sg.build_plan(cfg)
    host = sg.prepare_host_params(cfg, jax.tree.map(np.array, params))
    part = pt.partition(cfg, host, plan, budget_bytes=budget, batch=2, seq=64)
    store = HostModelStore(cfg, plan, params, OptimizerConfig(grad_clip=0.0),
                           part)
    return cfg, plan, part, store, params


def test_promote_demote_roundtrip_bit_exact():
    cfg, plan, part, store, params = _store()
    before = jax.tree.map(np.array, store.params)
    for shard in part.shards:
        own, shared, opt_state = store.promote_shard(shard)
        store.demote_shard(shard, own, opt_state)
    after = store.params
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_model_params_roundtrip_matches_original():
    cfg, plan, part, store, params = _store()
    rebuilt = store.model_params()
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(rebuilt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_shared_grad_accumulation():
    cfg, plan, part, store, params = _store()
    ref = sg.resolve_ref(store.params, plan.shared_refs["embed"])
    g1 = jax.tree.map(lambda a: np.ones_like(np.asarray(a)), ref)
    store.accumulate_shared_grads({"embed": g1})
    store.accumulate_shared_grads({"embed": g1})
    acc = store.shared_grad_acc["embed"]
    assert float(np.asarray(jax.tree.leaves(acc)[0]).max()) == 2.0
    before = np.array(jax.tree.leaves(ref)[0])
    store.step_shared()
    after = np.asarray(jax.tree.leaves(
        sg.resolve_ref(store.params, plan.shared_refs["embed"]))[0])
    assert not np.allclose(before, after)     # params moved
    assert store.shared_grad_acc == {}        # accumulator cleared


def test_device_budget_enforced():
    dev = DeviceMemory(0, budget_bytes=1000, buffer_frac=0.1)
    dev.charge_promotion(900, into_buffer=False)
    with pytest.raises(RuntimeError, match="over budget"):
        dev.charge_promotion(200, into_buffer=True)


def test_double_buffer_regions():
    dev = DeviceMemory(0, budget_bytes=1000)
    dev.charge_promotion(300, into_buffer=True)
    assert dev.buffered_bytes == 300 and dev.resident_bytes == 0
    dev.activate_buffer()
    assert dev.buffered_bytes == 0 and dev.resident_bytes == 300
    dev.charge_demotion(300)
    assert dev.resident_bytes == 0
    assert dev.stats.n_promotions == 1 and dev.stats.n_demotions == 1


def test_transfer_bytes_accounting():
    cfg, plan, part, store, params = _store()
    for shard in part.shards:
        tb = store.shard_transfer_bytes(shard)
        assert tb > 0
        # train transfer includes optimizer state (params x >= 2)
        own_only = sum(pt.tree_bytes(p) for p in store._own_params(shard)
                       if p is not None)
        assert tb >= 2 * own_only


def _demote_every_shard_changed(store, part):
    """Promote each shard, move its params and moments on the device (as a
    step would), fetch the result the old way (``np.array`` per leaf) for
    reference, then demote it.  Returns the reference and the device
    arrays the demotion consumed."""
    bump = jax.jit(lambda t: jax.tree.map(lambda a: a + 1, t))
    refs, consumed = {}, []
    for shard in part.shards:
        own, _, opt_state = store.promote_shard(shard)
        new_own, new_opt = bump((own, opt_state))
        refs[shard.index] = jax.tree.map(np.array, (new_own, new_opt))
        consumed += jax.tree.leaves((new_own, new_opt))
        store.demote_shard(shard, new_own, new_opt)
    return refs, consumed


def _assert_store_matches(store, plan, part, refs):
    for shard in part.shards:
        ref_own, ref_opt = refs[shard.index]
        for k, i in enumerate(range(shard.seg_lo, shard.seg_hi)):
            pref = plan.segments[i].param_ref
            if pref is None:
                continue
            got = sg.resolve_ref(store.params, pref)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref_own[k])):
                np.testing.assert_array_equal(a, b, strict=True)
        for a, b in zip(jax.tree.leaves(store.opt[shard.index]),
                        jax.tree.leaves(ref_opt)):
            np.testing.assert_array_equal(a, b, strict=True)


def test_demote_writes_into_the_same_writable_stacked_store():
    cfg, plan, part, store, params = _store()
    assert len(part.shards) > 1
    stacked = jax.tree.leaves(store.params["layers"])
    _demote_every_shard_changed(store, part)
    after = jax.tree.leaves(store.params["layers"])
    assert all(a is b for a, b in zip(stacked, after))
    assert all(a.flags.writeable for a in after)


def test_demote_is_bit_identical_to_the_old_fetch_and_outlives_the_device():
    cfg, plan, part, store, params = _store()
    refs, consumed = _demote_every_shard_changed(store, part)
    _assert_store_matches(store, plan, part, refs)
    # the moments are kept as they landed, read-only
    assert not any(a.flags.writeable for s in part.shards
                   for a in jax.tree.leaves(store.opt[s.index]))
    assert consumed and all(a.is_deleted() for a in consumed)
    # new device buffers where the freed ones were; the host values stand
    churn = [jnp.full(a.shape, -7, a.dtype) for a in consumed]
    jax.block_until_ready(churn)
    _assert_store_matches(store, plan, part, refs)


def test_demote_host_copies_are_the_stacked_param_bytes():
    cfg, plan, part, store, params = _store()
    before = store.host_copied_bytes
    _demote_every_shard_changed(store, part)
    # every layer sits in one shard and is copied once into the stack; the
    # final norm and the moments are kept as they land
    layer_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in jax.tree.leaves(store.params["layers"]))
    assert store.host_copied_bytes - before == layer_bytes
