"""The trace reduction, on a trace recorded on one TPU v5e (JAX 0.9): three
rounds of a small jitted matmul program, the Pallas paged-attention kernel,
a 64 MiB host->device copy and its fetch back, inside a host span named
``probe.window``.  The expected numbers were read off the trace's events by
hand."""

from __future__ import annotations

from pathlib import Path

import pytest

import bench_cells  # noqa: F401  (puts bench/ on sys.path)
import trace_reduce as T

PROBE = Path(__file__).resolve().parents[1] / "testdata" / "probe.xplane.pb"


@pytest.fixture(scope="module")
def probe():
    return T.reduce_trace(str(PROBE), window_span="probe.window")


def test_clock_offset_is_the_largest_enqueue_lead(probe):
    # run 15: DoEnqueueProgram at 43,737,304 ns, device start 42,365,388 ns
    assert probe.clock_offset_ns == 1_371_916


def test_window_and_busy_union(probe):
    assert probe.window_s == pytest.approx(544_731_417e-9, abs=1e-12)
    # the 21 XLA ops of six program runs, none overlapping: 16,128 + 15,877
    # + 16,082 ns (matmul program) and 14,686 + 14,628 + 14,686 ns (kernel)
    assert probe.busy_s == pytest.approx(92_087e-9, abs=1e-12)
    assert probe.chips == 1


def test_per_op_and_per_program_time(probe):
    assert probe.op_s["paged_attention"] == pytest.approx(
        (14_375 + 14_318 + 14_376) * 1e-9, abs=1e-12)
    assert probe.op_count["paged_attention"] == 3
    assert probe.op_count["copy-done"] == 6
    assert probe.module_count == {"jit_model_step": 3,
                                  "jit_paged_attention": 3}
    assert probe.module_s["jit_paged_attention"] == pytest.approx(
        (14_696 + 14_638 + 14_697) * 1e-9, abs=1e-12)


def test_transfers_are_listed_not_busy(probe):
    assert probe.transfers["h2d"]["n"] == 3
    assert probe.transfers["h2d"]["bytes"] == 3 * 64 * 2**20
    assert probe.transfers["d2h"]["bytes"] == 3 * 64 * 2**20


def test_idle_gaps_name_the_host_span(probe):
    label, seconds = probe.idle_gaps[0]
    assert label == "probe.d2h"          # the fetch back holds the host
    assert 0.17 < seconds < 0.19
    bd = probe.breakdown()
    assert bd["device_ops"][0][0] == "paged_attention"
    assert len(bd["idle_gaps"]) <= 10


def test_op_and_module_names():
    assert T.op_name("%paged_attention.1 = bf16[4,2] custom-call(x)") == \
        "paged_attention"
    assert T.op_name("%copy-start = (bf16[2]) copy-start(x)") == "copy-start"
    assert T.module_name("jit_paged_step(123456)") == "jit_paged_step"


def test_union_and_gaps_by_hand():
    iv = [(0, 10), (5, 20), (30, 40), (35, 36)]
    assert T.union_ns(iv) == 30
    assert T.gaps_ns(iv, 0, 50) == [(20, 30), (40, 50)]
    assert T.gaps_ns([], 3, 7) == [(3, 7)]


def test_mfu_reads_the_step_programs_device_time():
    """sharp.mfu: model FLOPs of the window's tokens over the device time
    of the programs the configuration names, not over the window."""
    from types import SimpleNamespace

    from harness import HERE, load_module
    from trace_reduce import Reduced
    mfu = load_module(HERE / "metrics" / "sharp.mfu.py")
    trace = Reduced(window_s=40.0, busy_s=2.0, chips=1,
                    module_s={"jit__unknown": 1.5, "jit__step_impl": 0.5,
                              "jit_add": 0.25})
    cell = SimpleNamespace(config={"programs": {
        "train_step": ["jit__unknown", "jit__step_impl"]}})
    outcome = SimpleNamespace(counters={"tokens": 4096,
                                        "train_flops_per_token": 6.4e9})
    ctx = SimpleNamespace(cell=cell, trace=trace, outcome=outcome,
                          device_kind="TPU v5 lite")
    assert mfu.read(ctx) == pytest.approx(100 * 4096 * 6.4e9 / (2.0 * 197e12))
    assert mfu.read(SimpleNamespace(**{**vars(ctx), "trace": None})) is None
    trace.module_s = {"jit_add": 0.25}
    assert mfu.read(ctx) is None
