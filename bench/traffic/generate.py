"""The one traffic generator: every mix is a JSON file of parameters
beside this file, read by ``load(name)`` and turned into work by the
function its ``kind`` names.

- ``train_batches``: a fixed-shape stream of language-model batches,
  ``batch`` rows of ``seq + 1`` uniform token ids per step, drawn from
  ``(seed, step)`` so that every step's rows differ and every seed does
  the same amount of work.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def load(name: str) -> dict:
    """The parameters of mix ``name`` (``<name>.json`` beside this file)."""
    path = HERE / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def train_batch(mix: dict, seed: int, step: int, vocab: int) -> dict:
    """Step ``step``'s batch: next-token language-model rows."""
    rng = np.random.default_rng([seed, 2, step])
    toks = rng.integers(0, vocab, (mix["batch"], mix["seq"] + 1),
                        dtype=np.int64).astype(np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
