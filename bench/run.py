"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Sets up the cell named in BENCHMARK.json, warms every shape it uses,
measures for ``--seconds``, checks what the timed path produced against
the plain reference, and prints one JSON object as the last line of
standard output: ``correct``, ``attempted``, ``failed``, ``metrics``
(the end-to-end metrics, or with ``--trace 1`` the per-layer ones),
``device`` and, traced, ``breakdown``; its last key, ``checks``, gives
each number compared beside its limit, as do the last lines of standard
error.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def _finite(x):
    """JSON has no infinity: a tail that never came is null."""
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite(v) for v in x]
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    harness.keep_compile_cache_in_checkout()      # before JAX is imported
    cell = harness.Cell.resolve(harness.load_benchmark(), args.workload)

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 2
    harness.info("start", workload=cell.name, seed=args.seed,
                 seconds=args.seconds, trace=args.trace, compile_cache=cache,
                 device_kind=repr(devices[0].device_kind))
    result = _finite(harness.run_cell(cell, args.seed, args.seconds,
                                      bool(args.trace)))
    print(f"correct={result['correct']}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} value={c['value']} limit={c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
