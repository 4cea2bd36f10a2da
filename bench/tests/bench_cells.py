"""Small copies of the benchmark's cells that a CPU test run can hold:
the real files, with the sizes of the program's smoke configurations, a
short traffic mix and a byte budget in place of the chip's."""

from __future__ import annotations

import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

SMALL_MODEL = {
    "bert-large-1b": {"n_layers": 2, "d_model": 128, "n_heads": 4,
                      "n_kv_heads": 4, "head_dim": 32, "d_ff": 256,
                      "vocab_size": 512},
}


def small_cell(workload: str) -> harness.Cell:
    cell = harness.Cell.resolve(harness.load_benchmark(), workload)
    cell.config = copy.deepcopy(cell.config)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.config["model"].update(SMALL_MODEL[cell.config["name"]])
    cell.config["job"]["budget_bytes_off_chip"] = 24 * 2**20
    cell.traffic.update(batch=2, seq=32)
    return cell


def run(cell: harness.Cell, seed: int = 3, seconds: float = 1.0,
        trace: bool = False) -> dict:
    return harness.run_cell(cell, seed, seconds, trace)
