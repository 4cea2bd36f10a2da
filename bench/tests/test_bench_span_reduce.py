"""Device idle time put down to the program's host spans
(``span_reduce``), on synthetic intervals worked out by hand and on a
trace recorded on one TPU v5e (JAX 0.9): the third step of a tiny SHARP
fine-tune (bert-large-1b's smoke widths with 3 layers, 3 shards, batch 2 x
seq 32) inside a host span named ``probe.window``.  To keep it small, the
file holds only the device's ``XLA Modules`` and ``XLA Ops`` lines (op
names cut to the instruction name, all ``op_name`` reads), the host's
Python thread and its ``DoEnqueueProgram`` events; the events themselves
are as recorded.  The expected numbers were read off its events by
hand."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

import bench_cells  # noqa: F401  (puts bench/ on sys.path)
import span_reduce as S
import trace_reduce as T
from harness import HERE, ROOT

NS = 1e-9
RECORDED = HERE / "testdata" / "sharp_spans.xplane.pb"


@pytest.fixture
def two_chips():
    """A window of [0, 100) ns.  Host: a promote span over [10, 40) with a
    JAX fetch nested in it, a demote span over [50, 80), a prepare span
    that starts before the window, a dispatch span after it, and the
    window's own span.  Chip 0 runs ops over [-5, 2), [20, 25), [60, 70)
    and [95, 110), so it idles over [2, 20), [25, 60) and [70, 95); chip 1
    runs nothing."""
    host = [(0, 100, "bench.window", {}),
            (10, 40, "spill.promote", {"step": 7, "shard": 0}),
            (15, 35, "np.asarray(jax.Array)", {}),
            (50, 80, "spill.demote", {"step": 7, "shard": 0}),
            (-10, 5, "session.prepare", {"step": 7}),
            (120, 130, "sharp.dispatch", {"step": 8})]
    chips = [[(-5, 2), (20, 25), (60, 70), (95, 110)], []]
    return S.build(host, chips, (0, 100))


def test_only_program_spans_in_the_window_are_kept(two_chips):
    assert [(s, e, n) for s, e, n, _ in two_chips.spans] == [
        (0, 5, "session.prepare"), (10, 40, "spill.promote"),
        (50, 80, "spill.demote")]
    assert two_chips.idle[0] == [(2, 20), (25, 60), (70, 95)]
    assert two_chips.idle[1] == [(0, 100)]
    assert two_chips.window_s == pytest.approx(100 * NS)


def test_idle_under_is_the_mean_over_chips(two_chips):
    # chip 0: [10, 20) + [25, 40) = 25 ns; chip 1: 30 ns
    assert two_chips.idle_under("spill.promote") == pytest.approx(27.5 * NS)
    # chip 0: [50, 60) + [70, 80) = 20 ns; chip 1: 30 ns
    assert two_chips.idle_under("spill.demote") == pytest.approx(25 * NS)
    # clipped to [0, 5): chip 0 idles over [2, 5), chip 1 over all of it
    assert two_chips.idle_under("session.prepare") == pytest.approx(4 * NS)
    assert two_chips.idle_under("sharp.dispatch") == 0
    assert two_chips.idle_s() == pytest.approx((78 + 100) / 2 * NS)


def test_span_seconds_and_summary(two_chips):
    assert two_chips.span_seconds("spill.promote", "spill.demote") == \
        pytest.approx(60 * NS)
    summary = two_chips.summary()
    assert summary["spans"]["spill.promote"] == {
        "n": 1, "host_s": pytest.approx(30 * NS),
        "idle_s": pytest.approx(27.5 * NS)}
    assert summary["phases"]["demote"]["idle_share"] == pytest.approx(25.0)
    assert summary["phases"]["loop"]["idle_s"] == pytest.approx(4 * NS)
    assert summary["unspanned_idle_s"] == pytest.approx(
        (89 - 27.5 - 25 - 4) * NS)
    assert summary["per_step"] == {"7": {"promote": pytest.approx(30 * NS),
                                         "demote": pytest.approx(30 * NS),
                                         "loop": pytest.approx(5 * NS)}}


def test_overlap_by_hand():
    assert S.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert S.overlap_ns([(0, 10), (5, 15)], [(0, 100)]) == 15
    assert S.overlap_ns([], [(0, 1)]) == 0


def test_run_mode_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/span_reduce.py", "--workload",
         "bert-large-1b.sharp-b8", "--seed", "3", "--seconds", "1",
         "--trace", "1"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode == 2
    assert "needs 1 TPU chip" in p.stderr


@pytest.fixture(scope="module")
def recorded():
    return S.reduce_spans(str(RECORDED), "probe.window")


def test_recorded_step_has_each_span_once_per_launch(recorded):
    names = [n for _, _, n, _ in recorded.spans]
    assert recorded.window_s == pytest.approx(181_266_951 * NS, abs=1e-12)
    assert {n: names.count(n) for n in set(names)} == {
        "session.prepare": 1, "sharp.batch": 1, "spill.promote": 6,
        "sharp.dispatch": 9, "sharp.loss_read": 1, "spill.shared_grads": 2,
        "spill.demote": 3, "spill.shared_step": 1, "session.finish": 1}
    assert {(m["shard"], m["dir"]) for _, _, n, m in recorded.spans
            if n == "spill.promote"} == {(k, d) for k in range(3)
                                         for d in ("fwd", "bwd")}
    assert all(m["step"] == 2 and m["model"] == 0
               for *_, m in recorded.spans)
    for (_, end, *_), (start, *_) in zip(recorded.spans, recorded.spans[1:]):
        assert start >= end


def test_recorded_idle_under_spans(recorded):
    # no XLA op runs during a promotion: the six spans of 12,779,770 +
    # 11,746,690 + 13,894,130 + 12,763,130 + 12,350,310 + 12,249,940 ns
    promote = 75_783_970 * NS
    assert recorded.span_seconds("spill.promote") == pytest.approx(
        promote, abs=1e-12)
    assert recorded.idle_under("spill.promote") == pytest.approx(
        promote, abs=1e-12)
    # the session's spans: 23,690 and 19,840 ns, the chip idle throughout
    assert recorded.idle_under("session.prepare", "session.finish") == \
        pytest.approx(43_530 * NS, abs=1e-12)
    # busy 160,504 ns (trace_reduce's union of the same ops), all of it
    # inside program spans, whose 25 durations sum to 176,755,630 ns
    assert recorded.idle_s() == pytest.approx(
        (181_266_951 - 160_504) * NS, abs=1e-12)
    summary = recorded.summary()
    assert summary["unspanned_idle_s"] == pytest.approx(
        (181_266_951 - 176_755_630) * NS, abs=1e-12)
    assert sum(p["idle_s"] for p in summary["phases"].values()) == \
        pytest.approx(recorded.idle_s() - summary["unspanned_idle_s"])


def test_recorded_idle_agrees_with_trace_reduce_and_gaps_name_spans():
    red = T.reduce_trace(str(RECORDED), window_span="probe.window")
    spans = S.reduce_spans(str(RECORDED), "probe.window")
    assert red.clock_offset_ns == 1_269_396
    assert red.busy_s == pytest.approx(160_504 * NS, abs=1e-12)
    assert red.idle_share() * red.window_s == pytest.approx(spans.idle_s())
    # the longest gaps fall in the demotions, whose spans enclose the
    # runtime's own fetch events
    assert [label for label, _ in red.idle_gaps[:3]] == ["spill.demote"] * 3
