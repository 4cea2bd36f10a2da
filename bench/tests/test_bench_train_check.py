"""The SHARP training cell's output check, driven end to end on the CPU at
the program's smoke sizes (the look for a chip skipped): a sound run is
correct, and each fault planted in the timed path makes ``correct`` come
out false.  One chip: no exchange between chips to leave out."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import bench_cells
import reftrain
from traffic.generate import train_batch

CELL = "bert-large-1b.sharp-b8"


@pytest.fixture
def fresh_programs(monkeypatch):
    """Compiled shard programs are shared per config; a planted fault must
    be traced anew."""
    from repro.core import sharp
    monkeypatch.setattr(sharp, "_FN_CACHE", {})
    return monkeypatch


def test_sound_run_is_correct_and_spilled(fresh_programs):
    cell = bench_cells.small_cell(CELL)
    r = bench_cells.run(cell, seed=2**31 + 5, seconds=0.3)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(r["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
    assert list(r)[-1] == "checks"


def test_step_that_returns_its_state_unchanged_is_caught(fresh_programs):
    from repro.optim import optimizers
    fresh_programs.setattr(optimizers, "update",
                           lambda cfg, params, grads, state, **kw:
                           (params, state))
    r = bench_cells.run(bench_cells.small_cell(CELL), seed=6, seconds=0.3)
    assert not r["correct"]
    assert r["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out_is_caught(fresh_programs):
    from repro.core import shard_graph
    full = shard_graph.softmax_xent

    def half(logits, labels, mask=None):
        h = logits.shape[0] // 2
        return full(logits[:h], labels[:h])
    fresh_programs.setattr(shard_graph, "softmax_xent", half)
    r = bench_cells.run(bench_cells.small_cell(CELL), seed=7, seconds=0.3)
    assert not r["correct"], r["checks"]


def test_control_in_float8_is_not_correct():
    """The reference in float8 put in the program's place fails a limit."""
    cell = bench_cells.small_cell(CELL)
    m, job, mix = cell.config["model"], cell.config["job"], cell.traffic

    def batches(k):
        return train_batch(mix, 8, k, m["vocab_size"])
    ref = reftrain.train(cell.reference, m, job, 8, batches,
                         job["check_steps"])
    ctrl = reftrain.train(cell.reference, m, job, 8, batches,
                          job["check_steps"], "fp8")
    got = reftrain.compare(ctrl, ref)
    limits = cell.config["checks"]
    assert any(got[k] > limits[k] for k in limits), got


def test_reference_weights_are_the_programs():
    """The reference draws the same weights from the seed as the program's
    initialiser, without importing it."""
    from repro.models import api
    cell = bench_cells.small_cell(CELL)
    m = cell.config["model"]
    prog = api.init_params(cell.arch_config(), jax.random.PRNGKey(2**33 + 1))
    k_embed, lkeys = cell.reference.layer_keys(m, 2**33 + 1)
    assert jnp.array_equal(prog["embed"]["table"],
                           cell.reference.init_embed(m, k_embed))
    for i, key in enumerate(lkeys):
        mine = cell.reference.init_layer(m, key)
        theirs = jax.tree.map(lambda a: a[i], prog["layers"])
        assert jax.tree.structure(mine) == jax.tree.structure(theirs)
        assert all(jax.tree.leaves(jax.tree.map(jnp.array_equal, mine,
                                                theirs)))
