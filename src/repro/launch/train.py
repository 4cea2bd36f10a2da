"""SPMD training launcher: a thin shell over ``repro.api.Session`` +
``SpmdTrainJob``.

Single-model pjit training over a mesh — the substrate Hydra's multi-model
layer schedules over sub-meshes of.  On the dev container it runs real steps
on the CPU device (reduced configs); on a pod the same driver drives the
production mesh.  The loop itself lives in ``repro.api.session._run_spmd``.

Usage:
  python -m repro.launch.train --arch qwen3-0.6b --smoke --steps 20
  python -m repro.launch.train --arch bert-large-1b --smoke --steps 200 \
      --batch 8 --seq 128 --log-every 10 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import json

from repro.api import Session, SpmdTrainJob
from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache


def job_from_args(args) -> SpmdTrainJob:
    cfg = get_config(args.arch, smoke=args.smoke)
    return SpmdTrainJob(
        cfg, steps=args.steps, batch=args.batch, seq=args.seq,
        accum=args.accum, lr=args.lr, optimizer=args.optimizer,
        seed=args.seed, data=args.data, mesh=args.mesh,
        multi_pod=args.multi_pod, log_every=args.log_every,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)


def train(args) -> dict:
    session = Session()
    jid = session.submit(job_from_args(args))
    report = session.run()
    return report.spmd[jid]


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data", default=None, help="token .bin (else synthetic)")
    ap.add_argument("--mesh", default="auto", choices=["auto", "production"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    args = ap.parse_args()
    out = train(args)
    print(json.dumps({k: v for k, v in out.items() if k != "history"}))


if __name__ == "__main__":
    main()
