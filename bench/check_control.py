"""Read a cell's output check on several seeds, beside its controls.

    python bench/check_control.py --workload <train cell> --seconds 0 \
        --controls fp8 half_batch --seeds 11 12 13

A control is the plain reference put in the program's place: computed in
the nearest precision below the one the configuration states (``fp8``,
float8 for the bfloat16 activations the configuration states), or with
half of each batch left out (``half_batch``).  For
each seed, in this one process, the cell runs as the benchmark runs it
(with a short window: ``--seconds``) and prints the program's readings
beside each control's: a sound check reads the program under its limits
and each control over at least one of them.  The benchmark's own runs
never run a control.  Needs the chips the cell asks for.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="+", default=["fp8"],
                    choices=("fp8", "half_batch"))
    args = ap.parse_args(argv)

    import harness
    harness.keep_compile_cache_in_checkout()      # before JAX is imported
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = harness.Cell.resolve(harness.load_benchmark(), args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"check_control: {cell.name} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2
    for seed in args.seeds:
        r = harness.run_cell(cell, seed, args.seconds, False,
                             controls=args.controls)
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "metrics": r["metrics"], "checks": r["checks"],
                          "control": r["control"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
