"""Device idle time put down to the program's own host spans.

The program records a profiler span (``jax.profiler.TraceAnnotation``) at
each host phase of a SHARP step, named ``spill.*``, ``sharp.*`` and
``session.*`` and carrying the step, model and, for a unit's spans, the
shard and direction (docs/architecture.md, "Tracing").  No program span
encloses another, so each idle nanosecond of a chip lies under at most one
of them and sums over spans count no second twice.  They sit on the host
plane, the clock to which ``trace_reduce`` aligns the device planes.

    python bench/span_reduce.py --file <trace.xplane.pb> [--window <span>]
    python bench/span_reduce.py [--keep <dir>] <arguments of bench/run.py>

The first reduces a trace and prints one JSON object (``Spans.summary``):
for each program span its count, host seconds and device idle seconds
under it; for each phase of ``PHASES`` its host and idle seconds and its
share of the window; the idle seconds under no program span; and each
step's host seconds by phase.  The second runs a cell through
``bench/run.py``: it prints the bytes the SHARP ledgers count device->host
in the window (``TransferStats.demoted_bytes``) on a ``[spans]`` line and,
in a ``--trace 1`` run, the same object on another, reducing the window's
trace before the harness deletes it (and copying the trace into ``<dir>``
first).
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
from dataclasses import dataclass
from pathlib import Path

from trace_reduce import (DEVICE_PREFIX, ENQUEUE, HOST_PLANE, MODULES_LINE,
                          OPS_LINE, _clip, _stats, gaps_ns, union_ns)

PROGRAM_PREFIXES = ("spill.", "sharp.", "session.")
# the three host phases of a SHARP step, each a set of program spans
PHASES = {
    "promote": ("spill.promote",),
    "demote": ("spill.demote", "spill.shared_grads", "spill.shared_step"),
    "loop": ("sharp.batch", "sharp.dispatch", "sharp.loss_read",
             "session.prepare", "session.finish"),
}


def overlap_ns(a, b) -> int:
    """Length of the points that both sets of intervals cover."""
    return union_ns(a) + union_ns(b) - union_ns(list(a) + list(b))


@dataclass
class Spans:
    """A window's program spans and each chip's idle intervals in it."""
    window_s: float
    spans: list          # (start_ns, end_ns, name, metadata), in the window
    idle: list           # per chip: [(start_ns, end_ns)] with no XLA op

    def _intervals(self, names) -> list[tuple[int, int]]:
        return [(s, e) for s, e, n, _ in self.spans if n in names]

    def idle_s(self) -> float:
        """Idle seconds of the window, mean over chips."""
        return sum(union_ns(g) for g in self.idle) / len(self.idle) * 1e-9

    def idle_under(self, *names: str) -> float:
        """Device idle seconds inside the union of the named spans, mean
        over chips."""
        under = self._intervals(names)
        return sum(overlap_ns(under, g) for g in self.idle) \
            / len(self.idle) * 1e-9

    def span_seconds(self, *names: str) -> float:
        """Host seconds inside the union of the named spans."""
        return union_ns(self._intervals(names)) * 1e-9

    def summary(self) -> dict:
        names = sorted({n for _, _, n, _ in self.spans})
        spans = {n: {"n": sum(1 for _, _, m, _ in self.spans if m == n),
                     "host_s": self.span_seconds(n),
                     "idle_s": self.idle_under(n)} for n in names}
        phases = {}
        for phase, members in PHASES.items():
            idle = self.idle_under(*members)
            phases[phase] = {"host_s": self.span_seconds(*members),
                             "idle_s": idle,
                             "idle_share": 100.0 * idle / self.window_s}
        per_step: dict = {}
        for s, e, n, meta in self.spans:
            phase = next((p for p, m in PHASES.items() if n in m), None)
            if phase is None or "step" not in meta:
                continue
            row = per_step.setdefault(str(meta["step"]), {})
            row[phase] = row.get(phase, 0.0) + (e - s) * 1e-9
        return {"window_s": self.window_s, "idle_s": self.idle_s(),
                "idle_share": 100.0 * self.idle_s() / self.window_s,
                "unspanned_idle_s": self.idle_s() - self.idle_under(*names),
                "spans": spans, "phases": phases, "per_step": per_step}


def build(host_events, chip_ops, window_ns) -> Spans:
    """``host_events``: (start, end, name, metadata) of the host plane;
    ``chip_ops``: per chip, the (start, end) of its XLA ops on the host's
    clock; ``window_ns``: the window's (start, end)."""
    lo, hi = window_ns
    spans = []
    for s, e, name, meta in host_events:
        c = _clip(s, e, lo, hi) if name.startswith(PROGRAM_PREFIXES) else None
        if c is not None:
            spans.append((c[0], c[1], name, meta))
    idle = [gaps_ns([c for s, e in ops if (c := _clip(s, e, lo, hi))], lo, hi)
            for ops in chip_ops]
    return Spans(window_s=(hi - lo) * 1e-9,
                 spans=sorted(spans, key=lambda sp: sp[:2]), idle=idle)


def reduce_spans(path: str, window_span: str) -> Spans:
    """Read one trace over the first host span named ``window_span``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    host = next(p for p in planes if p.name == HOST_PLANE)
    events, enqueue = [], {}
    for line in host.lines:
        for ev in line.events:
            s = int(ev.start_ns)
            events.append((s, s + int(ev.duration_ns), ev.name, _stats(ev)))
            if ev.name == ENQUEUE and "run_id" in events[-1][3]:
                rid = str(events[-1][3]["run_id"])
                enqueue[rid] = min(s, enqueue.get(rid, s))
    windows = [(s, e) for s, e, n, _ in events if n == window_span]
    if not windows:
        raise ValueError(f"{path}: no host span named {window_span!r}")
    chip_ops = []
    for plane in planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        # the device clock's lag, as trace_reduce finds it: a program
        # cannot start on the chip before the host enqueued it
        offset = 0
        for ev in (lines[MODULES_LINE].events if MODULES_LINE in lines
                   else ()):
            rid = str(_stats(ev).get("run_id"))
            if rid in enqueue:
                offset = max(offset, enqueue[rid] - int(ev.start_ns))
        chip_ops.append([(int(ev.start_ns) + offset,
                          int(ev.start_ns) + offset + int(ev.duration_ns))
                         for ev in (lines[OPS_LINE].events
                                    if OPS_LINE in lines else ())])
    if not chip_ops:
        raise ValueError(f"{path}: no {DEVICE_PREFIX}* plane in the trace")
    return build(events, chip_ops, min(windows))


def run_cell(argv: list[str], keep: str | None) -> int:
    """``bench/run.py`` with the window's trace also reduced by spans, and
    the device->host bytes the SHARP ledgers count in the window."""
    import harness
    harness.keep_compile_cache_in_checkout()      # before JAX is imported
    import run
    from repro.core.spilling import DeviceMemory

    ledgers, marks = [], {}
    init, start, stop, plain = (DeviceMemory.__init__, harness.Tracer.start,
                                harness.Tracer.stop, harness.Tracer.reduce)

    def demoted() -> int:
        return sum(dm.stats.demoted_bytes for dm in ledgers)

    def track(self, *args, **kw):
        init(self, *args, **kw)
        ledgers.append(self)

    def start_window(tracer):
        marks["d2h"] = demoted()
        start(tracer)

    def stop_window(tracer):
        stop(tracer)
        print(f"[spans] window_demoted_bytes={demoted() - marks['d2h']}",
              flush=True)

    def reduce(tracer):
        if tracer.enabled:
            path = sorted(tracer.dir.rglob("*.xplane.pb"))[-1]
            if keep:
                Path(keep).mkdir(parents=True, exist_ok=True)
                shutil.copy(path, Path(keep) / f"{tracer.dir.name}.xplane.pb")
            spans = reduce_spans(str(path), harness.WINDOW_SPAN)
            print("[spans] " + json.dumps(spans.summary()), flush=True)
        return plain(tracer)

    DeviceMemory.__init__ = track
    harness.Tracer.start = start_window
    harness.Tracer.stop = stop_window
    harness.Tracer.reduce = reduce
    return run.main(argv)


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--file", help="reduce this trace and run nothing")
    ap.add_argument("--window", default="bench.window")
    ap.add_argument("--keep", help="copy the window's trace here")
    args, rest = ap.parse_known_args(argv)
    if args.file:
        print(json.dumps(reduce_spans(args.file, args.window).summary()))
        return 0
    return run_cell(rest, args.keep)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
