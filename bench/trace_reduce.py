"""Reduce a JAX profiler trace (``*.xplane.pb``) to the numbers the
per-layer metrics read: device busy time, per-op and per-program device
time by stable name, host<->device transfers, and the longest idle gaps
with what the host was doing in them.

Layout of a TPU trace, as recorded on a TPU v5e with JAX 0.9:

- a plane ``/device:TPU:<i>`` per chip, with the lines ``XLA Modules``
  (one event per program execution, named ``jit_<fn>(<fingerprint>)``,
  carrying a ``run_id`` stat) and ``XLA Ops`` (one event per HLO op,
  named by its HLO text, ``%<op>.<n> = <shape> <opcode>(...)``; a Pallas
  kernel is a custom-call named after the kernel);
- a plane ``/host:CPU`` whose lines are host threads: the runtime's
  ``DoEnqueueProgram`` (with the same ``run_id``), transfers
  (``tpu::System::TransferToDevice`` / ``TransferFromDevice`` with a
  ``size`` stat), ``PjitFunction(<fn>)`` dispatches and the benchmark's
  own ``TraceAnnotation`` spans.

Device timestamps come on a clock that lags the host's by about a
millisecond.  A program cannot start on the device before the host
enqueued it, so the device events are shifted by the largest
``enqueue start - device start`` over the programs that carry a
``run_id`` on both sides.

Host<->device transfers do not count as busy: busy is the union of the
intervals of ``XLA Ops`` events, and transfers are reported apart.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"
TRANSFERS = {"tpu::System::TransferToDevice": "h2d",
             "tpu::System::TransferFromDevice": "d2h"}

_SUFFIX = re.compile(r"\.\d+$")


def op_name(hlo_text: str) -> str:
    """``%paged_attention.1 = bf16[..] custom-call(..)`` -> ``paged_attention``."""
    head = hlo_text.split(" = ", 1)[0].lstrip("%")
    return _SUFFIX.sub("", head)


def module_name(event_name: str) -> str:
    """``jit_paged_step(1234)`` -> ``jit_paged_step``."""
    return event_name.split("(", 1)[0]


def union_ns(intervals) -> int:
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The parts of [lo, hi) that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


@dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over chips
    chips: int
    op_s: dict = field(default_factory=dict)       # stable op name -> s
    op_count: dict = field(default_factory=dict)
    module_s: dict = field(default_factory=dict)   # program name -> s
    module_count: dict = field(default_factory=dict)
    transfers: dict = field(default_factory=dict)  # h2d/d2h -> {n, bytes, s}
    idle_gaps: list = field(default_factory=list)  # [(label, s)], longest first
    clock_offset_ns: int = 0

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, *names: str) -> float:
        return sum(self.module_s.get(n, 0.0) for n in names)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:top]]}


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def _clip(s: int, e: int, lo: int, hi: int):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def reduce_trace(path: str, window_span: str, top: int = 10) -> Reduced:
    """Reduce one trace over the first host span named ``window_span``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    host = next((p for p in planes if p.name == HOST_PLANE), None)
    devices = [p for p in planes if p.name.startswith(DEVICE_PREFIX)]
    if not devices:
        raise ValueError(f"{path}: no {DEVICE_PREFIX}* plane in the trace")

    host_events = []          # (start, end, name, line)
    enqueue = {}              # run_id -> earliest enqueue start
    transfers = {"h2d": [], "d2h": []}
    if host is not None:
        for line in host.lines:
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                name = ev.name
                host_events.append((s, e, name, line.name))
                if name == ENQUEUE:
                    rid = _stats(ev).get("run_id")
                    if rid is not None:
                        enqueue[str(rid)] = min(s, enqueue.get(str(rid), s))
                elif name in TRANSFERS:
                    size = int(_stats(ev).get("size", 0))
                    transfers[TRANSFERS[name]].append((s, e, size))

    spans = [(s, e) for s, e, n, _ in host_events if n == window_span]
    if not spans:
        raise ValueError(f"{path}: no host span named {window_span!r}")
    window_ns = min(spans)
    red_ops: dict[str, float] = {}
    red_cnt: dict[str, int] = {}
    red_mod: dict[str, float] = {}
    red_mcnt: dict[str, int] = {}
    busy, offsets, all_ops = [], [], []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        offset = 0
        mods = list(lines[MODULES_LINE].events) if MODULES_LINE in lines else []
        for ev in mods:
            rid = _stats(ev).get("run_id")
            if rid is not None and str(rid) in enqueue:
                offset = max(offset, enqueue[str(rid)] - int(ev.start_ns))
        offsets.append(offset)
        ops = []
        for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
            s = int(ev.start_ns) + offset
            ops.append((s, s + int(ev.duration_ns), op_name(ev.name)))
        lo, hi = window_ns
        kept = []
        for s, e, name in ops:
            c = _clip(s, e, lo, hi)
            if c is None:
                continue
            kept.append(c)
            red_ops[name] = red_ops.get(name, 0.0) + (c[1] - c[0]) * 1e-9
            red_cnt[name] = red_cnt.get(name, 0) + 1
        for ev in mods:
            s = int(ev.start_ns) + offset
            c = _clip(s, s + int(ev.duration_ns), lo, hi)
            if c is None:
                continue
            name = module_name(ev.name)
            red_mod[name] = red_mod.get(name, 0.0) + (c[1] - c[0]) * 1e-9
            red_mcnt[name] = red_mcnt.get(name, 0) + 1
        busy.append(union_ns(kept))
        all_ops.append(kept)

    lo, hi = window_ns
    window_s = (hi - lo) * 1e-9
    if window_s <= 0:
        raise ValueError(f"{path}: empty window {window_ns}")
    n = len(devices)
    for k in red_ops:
        red_ops[k] /= n
    for k in red_mod:
        red_mod[k] /= n

    # idle gaps of the first chip, labelled by the host event that covers
    # most of each gap (the window's own span and thread-long spans excluded)
    gaps = sorted(gaps_ns(all_ops[0], lo, hi), key=lambda g: g[0] - g[1])[:top]
    labelled = []
    for gs, ge in gaps:
        best, best_ov = "no host activity traced", 0
        for s, e, name, _ in host_events:
            if e <= gs or s >= ge or name == window_span:
                continue
            if s <= lo and e >= hi:
                continue
            ov = min(e, ge) - max(s, gs)
            if ov > best_ov:
                best, best_ov = name, ov
        labelled.append((best, (ge - gs) * 1e-9))

    tx = {}
    for kind, evs in transfers.items():
        inside = [(s, e, b) for s, e, b in evs if lo <= s < hi]
        tx[kind] = {"n": len(inside), "bytes": sum(b for _, _, b in inside),
                    "s": union_ns([(s, e) for s, e, _ in inside]) * 1e-9}
    return Reduced(window_s=window_s, busy_s=sum(busy) / n * 1e-9, chips=n,
                   op_s=red_ops, op_count=red_cnt, module_s=red_mod,
                   module_count=red_mcnt, transfers=tx, idle_gaps=labelled,
                   clock_offset_ns=max(offsets))
