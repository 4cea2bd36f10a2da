"""The SHARP loop's profiler spans and its device->host byte count, on a
tiny spilled fine-tune (3 shards, tied embeddings, 2 steps) traced with
``jax.profiler.trace`` and read back from the host plane."""

from __future__ import annotations

from collections import Counter
from pathlib import Path

import jax
import numpy as np
import pytest

from conftest import make_loader
from repro.api import HydraConfig, Session, TrainJob
from repro.configs import get_config
from repro.core import shard_graph as sg
from repro.core import spilling
from repro.core.partitioner import tree_bytes
from repro.core.spilling import DeviceMemory

PROGRAM = ("spill.", "sharp.", "session.")
STEPS = 2


def _session() -> Session:
    cfg = get_config("bert-large-1b", smoke=True).replace(n_layers=3)
    session = Session(HydraConfig(n_devices=1, device_budget_bytes=4 * 10**6,
                                  pilot=False))
    session.submit(TrainJob(cfg, make_loader(cfg, batch=2, seq=32), lr=1e-3,
                            epochs=1, steps_per_epoch=STEPS, seed=0, batch=2,
                            seq=32))
    session.train_execs          # build the host store before any trace
    return session


def _program_spans(trace_dir: Path) -> list[tuple]:
    """(start_ns, end_ns, name, metadata) of every program span, by start."""
    from jax.profiler import ProfileData
    (path,) = trace_dir.rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    host = next(p for p in pd.planes if p.name == "/host:CPU")
    spans = []
    for line in host.lines:
        for ev in line.events:
            if ev.name.startswith(PROGRAM):
                s = int(ev.start_ns)
                spans.append((s, s + int(ev.duration_ns), ev.name,
                              {k: v for k, v in ev.stats}))
    return sorted(spans)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    session = _session()
    trace_dir = tmp_path_factory.mktemp("sharp_trace")
    with jax.profiler.trace(str(trace_dir)):
        report = session.run()
    (ex,) = session.train_execs
    return report, ex, _program_spans(trace_dir)


def _keys(spans, name, *fields):
    return Counter(tuple(meta[f] for f in fields)
                   for _, _, n, meta in spans if n == name)


def test_one_promote_per_unit_and_one_demote_per_backward_unit(traced):
    _, ex, spans = traced
    shards = [s.index for s in ex.partition.shards]
    assert len(shards) == 3
    units = {(k, s, d) for k in range(STEPS) for s in shards
             for d in ("fwd", "bwd")}
    promote = _keys(spans, "spill.promote", "step", "shard", "dir")
    assert set(promote) == units and set(promote.values()) == {1}
    demote = _keys(spans, "spill.demote", "step", "shard", "dir")
    assert demote == Counter({(k, s, "bwd"): 1 for k in range(STEPS)
                              for s in shards})


def test_shared_grads_shared_step_and_batch_spans(traced):
    _, ex, spans = traced
    with_shared = [s.index for s in ex.partition.shards
                   if ex.store.shard_shared_names(s)]
    assert with_shared == [0, 2]           # the embedding and the tied head
    assert _keys(spans, "spill.shared_grads", "step", "shard", "dir") == \
        Counter({(k, s, "bwd"): 1 for k in range(STEPS) for s in with_shared})
    every_step = Counter({(k,): 1 for k in range(STEPS)})
    assert _keys(spans, "spill.shared_step", "step") == every_step
    assert _keys(spans, "sharp.batch", "step") == every_step
    assert _keys(spans, "sharp.loss_read", "step", "shard") == \
        Counter({(k, 2): 1 for k in range(STEPS)})


def test_dispatch_spans_match_the_launches(traced):
    _, ex, spans = traced
    shards = [s.index for s in ex.partition.shards]
    want = Counter({(k, s, d): 1 for k in range(STEPS) for s in shards
                    for d in ("fwd", "bwd", "step")})
    assert _keys(spans, "sharp.dispatch", "step", "shard", "dir") == want


def test_every_span_names_its_model_and_the_session_spans_the_run(traced):
    _, _, spans = traced
    assert {meta["model"] for _, _, _, meta in spans} == {0}
    for name in ("session.prepare", "session.finish"):
        assert _keys(spans, name, "step", "model") == Counter({(0, 0): 1})
    names = Counter(n for _, _, n, _ in spans)
    assert names["spill.promote"] == 2 * 3 * STEPS
    assert set(names) == {"spill.promote", "spill.demote",
                          "spill.shared_grads", "spill.shared_step",
                          "sharp.batch", "sharp.dispatch", "sharp.loss_read",
                          "session.prepare", "session.finish"}


def test_no_program_span_encloses_or_overlaps_another(traced):
    _, _, spans = traced
    for (_, end, a, _), (start, _, b, _) in zip(spans, spans[1:]):
        assert start >= end, (a, b)


def test_losses_are_bit_identical_without_the_profiler(traced):
    report, _, _ = traced
    plain = _session().run()
    assert plain.train.losses == report.train.losses
    assert len(plain.train.losses[0]) == STEPS


def test_demoted_bytes_count_what_one_step_fetches():
    """Backward units fetch their params and moments, the backward units of
    the embedding and the tied head fetch its gradient, and the shared step
    fetches the embedding and its moments; forward units fetch nothing."""
    session = _session()
    (ex,) = session.train_execs
    store, shards = ex.store, ex.partition.shards
    session.run(max_units=2 * len(shards))
    stats = session.devices[0].stats
    embed = sg.resolve_ref(store.params, store.plan.shared_refs["embed"])
    own = sum(tree_bytes(p) for s in shards for p in store._own_params(s)
              if p is not None)
    moments = sum(tree_bytes(store.opt[s.index]) for s in shards)
    grads = sum(tree_bytes(embed) for s in shards
                if store.shard_shared_names(s))
    shared_step = tree_bytes(embed) + tree_bytes(store.shared_opt["embed"])
    assert stats.demoted_bytes == own + moments + grads + shared_step
    assert stats.promoted_bytes == sum(
        store.shard_transfer_bytes(s) for s in shards) * 2
    assert stats.n_demotions == 2 * len(shards)
    assert session.devices[0].resident_bytes == 0


def _old_move_to_host(tree):
    """The fetch as it was: one blocking ``np.array`` copy per leaf."""
    def move(a):
        host = np.array(a)
        if isinstance(a, jax.Array):
            a.delete()
        return host
    return jax.tree.map(move, tree)


def _two_steps(session):
    (ex,) = session.train_execs
    copied = []
    for _ in range(STEPS):
        session.run(max_units=2 * len(ex.partition.shards))
        copied.append(session.devices[0].stats.host_copied_bytes)
    state = (ex.store.params, ex.store.opt, ex.store.shared_opt)
    return ex, list(ex.losses), jax.tree.leaves(state), copied


def test_two_steps_match_the_old_fetch_and_count_each_host_copy(monkeypatch):
    """Two full steps give the losses and the host store, bit for bit, of
    the same steps fetched the old way; one step copies on the host its
    layers' weights (into the stack) and the sum of the shared gradients,
    and keeps the moments, the final norm and the shared step's fetch as
    they land."""
    ex, losses, state, copied = _two_steps(_session())
    with monkeypatch.context() as m:
        m.setattr(spilling, "move_to_host", _old_move_to_host)
        _, old_losses, old_state, _ = _two_steps(_session())
    assert len(losses) == STEPS and losses == old_losses
    assert len(state) == len(old_state)
    for a, b in zip(state, old_state):
        np.testing.assert_array_equal(a, b, strict=True)
    store, shards = ex.store, ex.partition.shards
    embed = tree_bytes(sg.resolve_ref(store.params,
                                      store.plan.shared_refs["embed"]))
    n_grads = sum(1 for s in shards if store.shard_shared_names(s))
    one_step = tree_bytes(store.params["layers"]) + (n_grads - 1) * embed
    assert n_grads == 2 and copied == [one_step, 2 * one_step]


def test_charge_demotion_books_only_what_moved():
    dm = DeviceMemory(0, 1000)
    dm.charge_promotion(600, into_buffer=False)
    dm.charge_demotion(300, moved=0)         # a forward unit's release
    dm.charge_demotion(300, moved=120)
    dm.charge_fetch(50)                      # traffic with no residency
    assert dm.resident_bytes == 0
    assert dm.stats.demoted_bytes == 170 and dm.stats.n_demotions == 2
