"""The traffic generator: training batches by seed and step."""

from __future__ import annotations

import numpy as np
import pytest

import bench_cells  # noqa: F401  (puts bench/ on sys.path)
from traffic import generate as G

SEEDS = (0, 7, 2**31 + 12345, 2**40 + 3)


def test_train_batches_differ_by_step_and_repeat_by_seed():
    mix = G.load("sharp-b8")
    a0 = G.train_batch(mix, 5, 0, 30522)
    a1 = G.train_batch(mix, 5, 1, 30522)
    assert a0["tokens"].shape == (8, 512) and a0["tokens"].dtype == np.int32
    assert np.array_equal(a0["tokens"][:, 1:], a0["labels"][:, :-1])
    assert not np.array_equal(a0["tokens"], a1["tokens"])
    assert np.array_equal(a0["tokens"], G.train_batch(mix, 5, 0,
                                                      30522)["tokens"])
    rows = {r.tobytes() for r in np.concatenate([a0["tokens"],
                                                 a1["tokens"]])}
    assert len(rows) == 16


@pytest.mark.parametrize("seed", SEEDS)
def test_every_seed_gets_the_same_shapes_and_its_own_tokens(seed):
    mix = G.load("sharp-b8")
    base = G.train_batch(mix, 1, 0, 30522)
    got = G.train_batch(mix, seed, 0, 30522)
    assert got["tokens"].shape == base["tokens"].shape
    assert 0 <= int(got["tokens"].min()) and int(got["tokens"].max()) < 30522
    if seed != 1:
        assert not np.array_equal(got["tokens"], base["tokens"])


def test_unknown_mix_is_an_error():
    with pytest.raises(FileNotFoundError):
        G.load("no-such-mix")
