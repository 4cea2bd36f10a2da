"""Run a benchmark cell through ``bench/span_reduce.py`` and also print
the bytes the SHARP ledgers count as copied on the host in the window
(``TransferStats.host_copied_bytes``) on a ``[spans]`` line of their own.

    python scripts/span_copies.py [--keep DIR] <arguments of bench/run.py>
"""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))

import harness  # noqa: E402
import span_reduce  # noqa: E402


def main(argv: list[str]) -> int:
    harness.keep_compile_cache_in_checkout()      # before JAX is imported
    from repro.core.spilling import DeviceMemory

    ledgers, marks = [], {}
    init, start, stop = (DeviceMemory.__init__, harness.Tracer.start,
                         harness.Tracer.stop)

    def copied() -> int:
        return sum(dm.stats.host_copied_bytes for dm in ledgers)

    def track(self, *args, **kw):
        init(self, *args, **kw)
        ledgers.append(self)

    def start_window(tracer):
        marks["copied"] = copied()
        start(tracer)

    def stop_window(tracer):
        stop(tracer)
        print("[spans] window_host_copied_bytes="
              f"{copied() - marks['copied']}", flush=True)

    DeviceMemory.__init__ = track
    harness.Tracer.start = start_window
    harness.Tracer.stop = stop_window
    return span_reduce.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
