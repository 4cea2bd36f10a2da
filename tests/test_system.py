"""End-to-end behaviour of the full system: the paper's Fig-4 API drives a
real multi-model workload, and the dry-run launcher lowers reduced configs on
a forced multi-device host mesh (subprocess, so the device-count env is set
before jax initializes)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_paper_fig4_api():
    """The exact usage pattern of paper Fig. 4."""
    from conftest import make_loader
    from repro.configs import get_config
    from repro.core import HydraConfig, ModelOrchestrator, ModelTask

    cfg = get_config("bert-large-1b", smoke=True)
    task_0 = ModelTask(cfg, make_loader(cfg, seed=0), lr=1e-3, epochs=1,
                       steps_per_epoch=2, batch=2, seq=64)
    task_1 = ModelTask(cfg, make_loader(cfg, seed=1), lr=1e-4, epochs=1,
                       steps_per_epoch=2, batch=2, seq=64)
    orchestra = ModelOrchestrator([task_0, task_1],
                                  HydraConfig(n_devices=2,
                                              device_budget_bytes=8 * 10**6))
    report = orchestra.train_models()
    assert len(report.losses[0]) == 2 and len(report.losses[1]) == 2
    assert all(np.isfinite(l) for ls in report.losses.values() for l in ls)
    # trained params are reassembled into the standard tree
    params = orchestra.model_params(0)
    assert "layers" in params and "embed" in params


def _run_subprocess(code: str) -> str:
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_dryrun_small_mesh_all_families():
    """Reduced configs lower + compile on a forced 8-device (2,4) mesh —
    the in-process analogue of the 512-device production dry-run."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs import get_config, INPUT_SHAPES
from repro.models import api
from repro.optim import OptimizerConfig, init_state
from repro.sharding import specs as sh
from repro.training import make_train_step, make_decode_step

from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
for arch in ["qwen3-0.6b", "mixtral-8x22b", "xlstm-350m", "zamba2-1.2b",
             "whisper-medium"]:
    cfg = get_config(arch, smoke=True)
    ocfg = OptimizerConfig()
    params_s = jax.eval_shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0)))
    pshard = sh.to_shardings(mesh, sh.param_specs(cfg, params_s, mesh))
    opt_s = jax.eval_shape(lambda: init_state(ocfg, jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), params_s)))
    oshard = sh.to_shardings(mesh, sh.opt_state_specs(cfg, opt_s, mesh))
    import dataclasses
    from repro.configs.base import InputShape
    shape = InputShape("t", 128, 4, "train")
    batch_s = api.input_specs(cfg, shape, kind="train")
    bshard = sh.to_shardings(mesh, sh.batch_specs(cfg, batch_s, mesh))
    fn = jax.jit(make_train_step(cfg, ocfg),
                 in_shardings=(pshard, oshard, bshard))
    compiled = fn.lower(params_s, opt_s, batch_s).compile()
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes >= 0
    # decode too
    state_s = jax.eval_shape(lambda: api.init_decode_state(cfg, 4, 128))
    sshard = sh.to_shardings(mesh, sh.decode_state_specs(cfg, state_s, mesh))
    tok = jax.ShapeDtypeStruct((4, 1), jnp.int32)
    dfn = jax.jit(make_decode_step(cfg), in_shardings=(pshard, sshard, None))
    dfn.lower(params_s, state_s, tok).compile()
    print("OK", arch)
"""
    out = _run_subprocess(code)
    assert out.count("OK") == 5


def test_train_launcher_end_to_end():
    from repro.launch.train import train

    class A:
        arch = "qwen3-0.6b"; smoke = True; steps = 6; batch = 2; seq = 64
        accum = 1; lr = 1e-3; optimizer = "adamw"; seed = 0; data = None
        mesh = "auto"; multi_pod = False; log_every = 2
        ckpt_dir = None; ckpt_every = 100

    out = train(A())
    assert np.isfinite(out["final_loss"])
    assert out["history"][-1]["loss"] < out["history"][0]["loss"] + 1.0


def test_serve_launcher_end_to_end():
    from repro.launch.serve import serve

    class A:
        arch = "qwen3-0.6b"; smoke = True; batch = 2
        prompt_len = 8; gen = 4; seed = 0
        capacity = 2; max_seq = 0; kv_budget_mb = 0
        stagger = 0; scheduler = "lrtf"

    out = serve(A())
    assert len(out["requests"]) == 2
    assert all(r["n_generated"] == 4 and r["status"] == "finished"
               for r in out["requests"])
    assert out["engines"]["qwen3-0.6b"]["n_completed"] == 2
    assert len(out["sample"]) == 4
