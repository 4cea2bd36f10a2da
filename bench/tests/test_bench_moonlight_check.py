"""The Moonlight cell's output check, driven end to end on the CPU at a
small size of the same structure (one dense and two MoE blocks, 8 routed
experts of which 4 are held, top-2, spilled in several shards): a sound
run is correct, each control fails a limit, and the reference draws the
program's weights.

The program computes in float32 here.  The limits are set for the cell's
bfloat16 program at 32,768 tokens a step; at this test's 64 tokens,
bfloat16 moves the second step's loss by 0.0013 to 0.024 from the f32
reference over three seeds (route flips at near-ties, and Adam's first
step following the sign of every small gradient), so this test holds the
check's plumbing (leaf names from the shard plan, the reference trainer,
the comparison) to the limits, not bfloat16's rounding at a toy size."""

from __future__ import annotations

import copy
from functools import partial

import jax
import jax.numpy as jnp
import pytest

import bench_cells  # noqa: F401  (puts bench/ and src/ on sys.path)
import harness

CELL = "moonlight-16b-a3b.sharp-b8-s4k"
SMALL = {"n_layers": 3, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
         "head_dim": 24, "d_ff": 128, "vocab_size": 512, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "n_experts": 8, "n_experts_held": 4, "expert_offset": 4,
         "top_k": 2, "moe_d_ff": 32, "dtype": "float32"}


@pytest.fixture(scope="module")
def cell():
    c = harness.Cell.resolve(harness.load_benchmark(), CELL)
    c.config = copy.deepcopy(c.config)
    c.traffic = dict(c.traffic, batch=2, seq=32)
    c.config["model"].update(SMALL)
    c.config["job"].update(budget_bytes_off_chip=6 * 2**20, warm_steps=0)
    return c


@pytest.fixture(scope="module")
def readings(cell):
    """One short run of the cell with both controls read beside it."""
    return harness.run_cell(cell, 2**31 + 11, 0.05, False,
                            controls=("fp8", "half_batch"))


def test_sound_run_is_correct_and_spilled(readings):
    r = readings
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("control", ["fp8", "half_batch"])
def test_control_is_not_correct(readings, cell, control):
    got = readings["control"][control]
    limits = cell.config["checks"]
    assert any(got[k] > limits[k] for k in limits), got


def test_reference_weights_are_the_programs(cell):
    """The reference draws the same weights from the seed as the program's
    initialiser, without importing it."""
    from repro.models import api
    m = cell.config["model"]
    seed = 2**33 + 3
    prog = jax.jit(partial(api.init_params, cell.arch_config()))(
        jax.random.PRNGKey(seed))
    ref = jax.jit(lambda: cell.reference.init_params(m, seed))()
    blocks = ([jax.tree.map(lambda a: a[0], prog["dense"])]
              + [jax.tree.map(lambda a, i=i: a[i], prog["layers"])
                 for i in range(m["n_layers"] - 1)])
    for mine, theirs in ((ref["embed"], prog["embed"]),
                         (ref["head"], prog["head"]),
                         (ref["blocks"], blocks)):
        assert jax.tree.structure(mine) == jax.tree.structure(theirs)
        assert all(jax.tree.leaves(jax.tree.map(jnp.array_equal, mine,
                                                theirs)))


def test_precompile_covers_every_program_of_the_trainer(cell):
    """After ``precompile``, the reference's training of a whole batch runs
    only programs compiled ahead of time: none compiles on first call."""
    from traffic.generate import train_batch
    ref = cell.reference
    m, job, mix = cell.config["model"], cell.config["job"], cell.traffic
    ref.precompile(m, job, mix["batch"], mix["seq"])
    P = ref._programs(ref._json(m), ref._json(job), "f32")
    progs = [ref._tree_norms, *P.init_b.values(),
             *(v for v in vars(P).values() if isinstance(v, ref._Program))]

    def missed(*args):
        raise AssertionError("a program compiled on first call")
    kept = [p.jit for p in progs]
    try:
        for p in progs:
            p.jit = missed
        got = ref.train(m, job, 5, lambda k: train_batch(
            mix, 5, k, m["vocab_size"]), 2)
    finally:
        for p, jit in zip(progs, kept):
            p.jit = jit
    assert len(got["losses"]) == 2 and got["change_norm"]
