"""Multi-pod dry-run: prove every (architecture × input shape × mesh)
combination lowers, partitions, and fits — with zero real allocation.

For each combination this driver:
  1. builds ShapeDtypeStruct stand-ins for params / optimizer state / inputs,
  2. jits the right step (train / prefill / decode) with the sharding rules
     from ``repro.sharding.specs``,
  3. ``.lower().compile()`` on the production mesh,
  4. records ``memory_analysis()`` (fits?), ``cost_analysis()`` (FLOPs/bytes)
     and the collective mix parsed from the partitioned HLO,
  5. appends a JSON record consumed by §Dry-run / §Roofline of EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  python -m repro.launch.dryrun --all --out results/dryrun.jsonl
  python -m repro.launch.dryrun --all --multi-pod
"""

import os
# 512 virtual CPU devices stand in for the pod; never the attached chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import re
import time
import traceback
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config
from repro.launch.mesh import make_production_mesh
from repro.models import api
from repro.optim import OptimizerConfig, init_state
from repro.sharding import specs as sh
from repro.training import (decode_window_for, make_decode_step,
                            make_prefill_step, make_train_step)

COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\b")


def _sds_tree(f, *args, **kw):
    return jax.eval_shape(f, *args, **kw)


def collective_bytes(hlo_text: str, trip_scale: dict[str, int]) -> dict:
    """Sum operand bytes of collective ops in partitioned HLO.

    Collectives inside while-loop body computations are scaled by the scan
    trip count (layer count), since XLA's cost/text shows the body once.
    """
    shape_re = re.compile(r"(bf16|f32|f16|s32|u32|s8|u8|f64|pred)\[([\d,]*)\]")
    dtype_bytes = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s32": 4,
                   "u32": 4, "s8": 1, "u8": 1, "pred": 1}

    def op_bytes(line: str) -> int:
        # output shape(s) of the op — for collectives output size ~ operand
        total = 0
        head = line.split("=", 1)[0] + "=" + \
            line.split("=", 1)[1].split("(", 1)[0] if "=" in line else line
        for m in shape_re.finditer(head):
            dt, dims = m.groups()
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * dtype_bytes[dt]
        return total

    # split into computations
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo_text.splitlines():
        m = re.match(r"^\s*(%?[\w\.\-_]+)\s*\(.*\)\s*->.*{", line)
        if m:
            cur = m.group(1).lstrip("%")
            comps[cur] = []
        elif cur is not None:
            comps[cur].append(line)

    per_kind: dict[str, float] = {}
    count = 0
    for name, lines in comps.items():
        scale = 1
        for pat, s in trip_scale.items():
            if pat in name:
                scale = s
                break
        for line in lines:
            m = COLLECTIVE_RE.search(line)
            if m and "=" in line and not line.strip().startswith("ROOT tuple"):
                kind = m.group(1)
                if "-done" in line.split("=")[1].split("(")[0]:
                    continue   # count start, not done
                b = op_bytes(line)
                per_kind[kind] = per_kind.get(kind, 0) + b * scale
                count += scale
    per_kind["total"] = sum(v for k, v in per_kind.items())
    per_kind["n_ops"] = count
    return per_kind


def build_step(cfg, shape, mesh):
    """Returns (jitted_fn, example_args_as_SDS) for the shape's step kind."""
    ocfg = OptimizerConfig(kind="adamw", lr=1e-4, grad_clip=1.0)
    params_s = _sds_tree(lambda: api.init_params(cfg, jax.random.PRNGKey(0)))
    pspec = sh.param_specs(cfg, params_s, mesh)
    pshard = sh.to_shardings(mesh, pspec)

    if shape.kind == "train":
        batch_s = api.input_specs(cfg, shape, kind="train")
        bshard = sh.to_shardings(mesh, sh.batch_specs(cfg, batch_s, mesh))
        opt_s = _sds_tree(lambda: init_state(
            ocfg, jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                               params_s)))
        oshard = sh.to_shardings(mesh, sh.opt_state_specs(cfg, opt_s, mesh))
        # micro-batch = one sequence per data shard; the rest accumulates
        data_size = 1
        for ax in ("pod", "data"):
            if ax in mesh.axis_names:
                data_size *= mesh.shape[ax]
        accum = max(1, shape.global_batch // data_size)
        step = make_train_step(cfg, ocfg, accum_steps=accum, mesh=mesh)
        fn = jax.jit(step, in_shardings=(pshard, oshard, bshard),
                     out_shardings=(pshard, oshard, None),
                     donate_argnums=(0, 1))
        return fn, (params_s, opt_s, batch_s), {"layers": cfg.n_layers,
                                                "accum": accum}

    if shape.kind == "prefill":
        batch_s = api.input_specs(cfg, shape, kind="prefill")
        batch_s.pop("labels", None)
        bshard = sh.to_shardings(mesh, sh.batch_specs(cfg, batch_s, mesh))
        step = make_prefill_step(cfg)
        fn = jax.jit(step, in_shardings=(pshard, bshard), out_shardings=None)
        return fn, (params_s, batch_s), {"layers": cfg.n_layers}

    # decode
    window = decode_window_for(cfg, shape)
    state_s = _sds_tree(lambda: api.init_decode_state(
        cfg, shape.global_batch, shape.seq_len))
    sshard = sh.to_shardings(mesh, sh.decode_state_specs(cfg, state_s, mesh))
    tok_s = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    step = make_decode_step(cfg, window=window)
    fn = jax.jit(step, in_shardings=(pshard, sshard, None),
                 out_shardings=(None, sshard), donate_argnums=(1,))
    return fn, (params_s, state_s, tok_s), {"layers": cfg.n_layers}


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            skip_notes: bool = True) -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    rec: dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "family": cfg.family, "kind": shape.kind,
    }
    t0 = time.time()
    try:
        from repro.sharding.context import activation_axes
        mesh = make_production_mesh(multi_pod=multi_pod)
        fn, args, meta = build_step(cfg, shape, mesh)
        # shard_map MoE wins on serving paths; GSPMD is leaner under vjp
        with activation_axes(mesh, moe_shardmap=(shape.kind != "train")):
            lowered = fn.lower(*args)
            compiled = lowered.compile()
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        trips = {"while": meta["layers"], "body": meta["layers"],
                 "cond": meta["layers"]}
        coll = collective_bytes(hlo, trips)
        rec.update(
            status="ok",
            compile_s=round(time.time() - t0, 1),
            bytes_per_device={
                "arguments": ma.argument_size_in_bytes,
                "output": ma.output_size_in_bytes,
                "temp": ma.temp_size_in_bytes,
                "alias": ma.alias_size_in_bytes,
                "peak": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                         + ma.temp_size_in_bytes - ma.alias_size_in_bytes),
            },
            hlo_flops_per_device=ca.get("flops", 0.0),
            hlo_bytes_per_device=ca.get("bytes accessed", 0.0),
            collectives=coll,
            scan_trip=meta["layers"],
        )
        print(f"OK   {arch:24s} {shape_name:12s} {rec['mesh']:8s} "
              f"peak={rec['bytes_per_device']['peak']/1e9:6.2f}GB "
              f"flops={rec['hlo_flops_per_device']:.3e} "
              f"coll={coll.get('total', 0)/1e9:.2f}GB  "
              f"({rec['compile_s']}s)")
    except Exception as e:
        rec.update(status="fail", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:],
                   compile_s=round(time.time() - t0, 1))
        print(f"FAIL {arch:24s} {shape_name:12s} {rec['mesh']:8s} {e}")
    return rec


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


# ---------------------------------------------------------------------------
# Session plan dry-run: the partition/spill/schedule view of a workload,
# without executing a single unit.  The Plan written here is the SAME object
# repro.api.Session.run consumes — plan once, inspect, then execute.
# ---------------------------------------------------------------------------

def _plan_loader(cfg, batch, seq, seed):
    from repro.models import api as mapi

    class L:
        def __iter__(self):
            def gen():
                i = 0
                while True:
                    k = jax.random.fold_in(jax.random.PRNGKey(seed), i)
                    yield mapi.make_dummy_batch(cfg, batch, seq, key=k)
                    i += 1
            return gen()

    return L()


def plan_dryrun(args) -> dict:
    """Build a Session over --arch TrainJobs, emit its Plan as JSON, and
    verify the JSON round-trips byte-identically."""
    from repro.api import Plan, Session, TrainJob
    from repro.core.sharp import HydraConfig

    archs = [a.strip() for a in (args.arch or "qwen3-0.6b").split(",")
             if a.strip()]
    # what-if pricing: --profile plans against another machine's measured
    # facts (loaded without the freshness gate — a foreign fingerprint is
    # the point); the default (None, not "auto") pins analytic pricing so
    # the smoke plan is byte-stable regardless of any cached local profile
    profile = None
    if getattr(args, "profile", None):
        from repro.profiler import load_facts
        profile = load_facts(args.profile, require_fresh=False)
    session = Session(HydraConfig(
        n_devices=args.n_devices,
        device_budget_bytes=int(args.budget_mb * 10**6)),
        profile=profile)
    for i, arch in enumerate(archs):
        cfg = get_config(arch, smoke=args.smoke)
        session.submit(TrainJob(cfg, _plan_loader(cfg, 2, 64, seed=i),
                                epochs=1, steps_per_epoch=2, seed=i,
                                batch=2, seq=64))
    plan = session.plan()

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    plan.save(args.out)
    reloaded = Plan.load(args.out)
    if reloaded.to_json() != plan.to_json():
        raise AssertionError(f"plan JSON does not round-trip ({args.out})")

    summary = plan.summary()
    print(json.dumps(summary))
    est = summary["est_makespan_s"]
    print(f"plan -> {args.out}  ({len(plan.jobs)} jobs, "
          f"est makespan {est:.3e}s, round-trip OK)" if est is not None
          else f"plan -> {args.out}  ({len(plan.jobs)} jobs, round-trip OK)")
    return summary


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    # session-plan mode (repro.api): partition/spill/schedule, no execution
    ap.add_argument("--plan", action="store_true",
                    help="emit a Session Plan JSON instead of lowering HLO")
    ap.add_argument("--smoke", action="store_true",
                    help="(--plan) reduced configs")
    ap.add_argument("--n-devices", type=int, default=2,
                    help="(--plan) virtual device count")
    ap.add_argument("--budget-mb", type=float, default=18,
                    help="(--plan) per-device budget, MB")
    ap.add_argument("--profile", default=None,
                    help="(--plan) MachineFacts JSON to price the plan "
                    "with — the what-if tool; default analytic")
    args = ap.parse_args()

    if args.plan:
        if args.out == "results/dryrun.jsonl":
            args.out = "results/plan.json"
        plan_dryrun(args)
        return

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    combos = []
    archs = ASSIGNED_ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for mp in meshes:
        for a in archs:
            for s in shapes:
                combos.append((a, s, mp))

    with open(args.out, "a") as f:
        for a, s, mp in combos:
            rec = run_one(a, s, multi_pod=mp)
            f.write(json.dumps(rec) + "\n")
            f.flush()


if __name__ == "__main__":
    main()
