"""On-chip smoke test: SHARP training and paged serving on one TPU.

    python3 chip_smoke.py                # one chip: device, train, serve
    python3 chip_smoke.py --four-chips   # four chips: the SPMD trainer on
                                         # a 2x2 mesh against a 1x1 mesh

Drives the system once through the entry points a user calls
(``hydra.Session`` -> ``TrainJob`` / ``ServeJob`` -> SHARP executor /
serving engine) at full published widths, with random weights made from a
seed.  Every phase asserts its own results; any failure raises and the
script exits non-zero.  There is no CPU fallback: without a TPU it exits
non-zero at the device phase and prints no result.  The last line of
standard output is one JSON object naming the device.

The persistent compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set,
else ``<repo>/.jax_cache``, so a second run compiles less; each phase
prints the seconds spent in XLA compilation (cache reads included).
"""

from __future__ import annotations

import argparse
import gc
import http.client
import importlib.metadata
import json
import math
import os
import resource
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import hydra  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.data import DataConfig, SyntheticTokens  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import api  # noqa: E402
from repro.serving import HydraHTTPServer, MultiModelServer  # noqa: E402

SEED = 0

# train phase: the paper's BERT, one fine-tune (see train_phase)
TRAIN_ARCH = "bert-large-1b"
TRAIN_LRS = (1e-4,)
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 512, 2
# The ledger prices a shard at 4 copies of its parameters (params, grads,
# two Adam moments); the executor's optimizer step briefly holds 7 (old
# and new params and moments, plus grads) and XLA needs workspace on top:
# at half of bytes_limit the device peaked at 1.66x the budget (PERF.md).
TRAIN_BUDGET_FRACTION = 0.5

# serve phase: paged qwen3-0.6b behind the HTTP front end
SERVE_ARCH = "qwen3-0.6b"
CAPACITY, BLOCK_SIZE, MAX_SEQ = 8, 16, 1024
PROMPT_LEN, MAX_TOKENS, N_REQUESTS = 128, 32, 8
EXPECTED_PAGED_IMPL = "pallas"
KERNEL_MARKER = "tpu_custom_call"
# f32 activations at HIGHEST precision: Pallas and jnp differ only in the
# order of their sums; measured 4.24e-05 at a 3.24 logit peak on the chip
F32_LOGIT_TOL = 1e-3
# bf16 Pallas logits may be at most this much further (RMS over all
# logits) from the f32 jnp truth than bf16 jnp logits are
BF16_ERR_RATIO = 1.5

# four-chip phase: the SPMD trainer, 2x2 mesh against 1x1
SPMD_ARCH = "qwen3-0.6b"
SPMD_BATCH, SPMD_SEQ, SPMD_STEPS = 4, 512, 3
# Measured 2x2-vs-1x1 gaps on TPU v5e: losses 4.18e-04 (about 12.14, and
# moving 0.014 over the 3 steps), gradient norms 9.8e-04 relative (each
# step moves them about 8.5%).  The bounds are ~5x the gaps and far under
# one step's movement, so a skipped update or a missing all-reduce fails.
SPMD_LOSS_TOL = 2e-3
SPMD_GNORM_RTOL = 5e-3


def check(ok: bool, what) -> None:
    """An assert that still holds under ``python -O``."""
    if not ok:
        raise AssertionError(what)


def host_peak_rss_gib() -> float:
    """Peak resident host memory of this process so far: SHARP keeps every
    model's master copy and optimizer state in host DRAM."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


def info(phase: str, **fields) -> None:
    """One informational line; never the result line."""
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


class CompileClock:
    """Seconds JAX spends in backend compilation (persistent-cache reads
    included), from JAX's own monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> tuple[float, int]:
        return self.seconds, self.cache_hits

    def since(self, mark: tuple[float, int]) -> dict:
        return {"compile_s": round(self.seconds - mark[0], 3),
                "cache_hits": self.cache_hits - mark[1]}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def device_phase(n_chips: int):
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {dev.platform!r} "
                         "devices); this script measures nothing elsewhere")
    if len(devices) != n_chips:
        raise SystemExit(f"chip_smoke: expected {n_chips} chip(s), JAX "
                         f"found {len(devices)}")
    host_dram = os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
    info("device", kind=repr(dev.device_kind), count=len(devices),
         jax=jax.__version__, libtpu=importlib.metadata.version("libtpu"),
         host_dram_gib=round(host_dram / 2**30, 1),
         hbm_bytes_limit=dev.memory_stats()["bytes_limit"])
    return dev


def train_phase(dev, clock: CompileClock):
    """The paper's own job: fine-tunes of one base at TRAIN_LRS, spilled
    shard by shard through one chip under SHARP.

    One fine-tune: each full bert-large-1b host store holds 12.9 GB (fp32
    params and two AdamW moments), and a run of two on a one-chip TPU v5e
    host (45 GiB of DRAM) peaked at 47.3 GiB resident."""
    cfg = get_config(TRAIN_ARCH)
    limit = dev.memory_stats()["bytes_limit"]
    budget = int(limit * TRAIN_BUDGET_FRACTION)
    session = hydra.Session(hydra.HydraConfig(
        n_devices=1, device_budget_bytes=budget, seed=SEED), profile=None)
    for i, lr in enumerate(TRAIN_LRS):
        data = SyntheticTokens(DataConfig(
            batch_size=TRAIN_BATCH, seq_len=TRAIN_SEQ,
            vocab_size=cfg.vocab_size, seed=SEED + i))
        session.submit(hydra.TrainJob(
            cfg, data, lr=lr, epochs=1, steps_per_epoch=TRAIN_STEPS,
            batch=TRAIN_BATCH, seq=TRAIN_SEQ, seed=SEED))
    mark = clock.mark()
    t0 = time.perf_counter()
    plan = session.plan()       # builds every model's host store
    info("train", plan_s=round(time.perf_counter() - t0, 3),
         host_peak_rss_gib=round(host_peak_rss_gib(), 2))
    report = session.run(plan)
    wall = time.perf_counter() - t0

    jobs = plan.summary()["jobs"]
    shards = {jid: rec["n_shards"] for jid, rec in jobs.items()}
    stats = report.train.transfer[0]
    losses = report.train.losses
    n_params = api.param_count(jax.eval_shape(
        lambda: api.init_params(cfg, jax.random.PRNGKey(SEED))))
    info("train", arch=TRAIN_ARCH, params=n_params, budget_bytes=budget,
         shards=shards, promoted_bytes=stats.promoted_bytes,
         demoted_bytes=stats.demoted_bytes,
         host_copied_bytes=stats.host_copied_bytes,
         units=report.train.units_executed, wall_s=round(wall, 3),
         peak_bytes_in_use=dev.memory_stats()["peak_bytes_in_use"],
         host_peak_rss_gib=round(host_peak_rss_gib(), 2),
         **clock.since(mark))
    for mid, ls in losses.items():
        info("train", model=mid, lr=TRAIN_LRS[mid], losses=ls)
    check(all(n > 1 for n in shards.values()),
          f"model fits unspilled: {shards}")
    check(stats.promoted_bytes > 0, "no shard was promoted")
    check(len(losses) == len(TRAIN_LRS), f"losses of {len(losses)} models")
    for mid, ls in losses.items():
        check(len(ls) == TRAIN_STEPS and all(math.isfinite(x) for x in ls),
              f"model {mid} losses {ls}")


def _post(address, body: dict) -> tuple[dict, float, float]:
    """One /v1/completions request; returns (final payload, ttft_s, e2e_s).
    A streamed request's payload is its token chunks folded into the
    non-streaming shape."""
    host, port = address
    conn = http.client.HTTPConnection(host, port, timeout=600)
    t0 = time.perf_counter()
    ttft = None
    try:
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise AssertionError(f"HTTP {resp.status}: {resp.read()[:500]}")
        if not body.get("stream"):
            out = json.loads(resp.read().decode())
            return out, None, time.perf_counter() - t0
        ids = []
        while True:
            raw = resp.readline()
            if not raw:
                break
            line = raw.rstrip(b"\n")
            if not line or line.startswith(b":"):
                continue
            data = line[len(b"data: "):]
            if data == b"[DONE]":
                break
            choice = json.loads(data)["choices"][0]
            if "token_id" in choice:
                ttft = ttft or time.perf_counter() - t0
                ids.append(choice["token_id"])
        return ({"choices": [{"token_ids": ids}]}, ttft,
                time.perf_counter() - t0)
    finally:
        conn.close()


def serve_phase(clock: CompileClock):
    """Paged serving behind the HTTP front end: half the requests stream."""
    cfg = get_config(SERVE_ARCH)
    session = hydra.Session(hydra.HydraConfig(n_devices=1, seed=SEED),
                            profile=None)
    job = hydra.ServeJob(cfg, seed=SEED, backend="paged",
                         block_size=BLOCK_SIZE, capacity=CAPACITY,
                         max_seq=MAX_SEQ)
    jid = session.submit(job)
    mark = clock.mark()
    t0 = time.perf_counter()
    eng = session.engine(jid)
    http = HydraHTTPServer(MultiModelServer({cfg.name: eng}), port=0,
                           model_options={cfg.name: job.http_options()})
    rng = np.random.default_rng(SEED)
    prompts = rng.integers(0, cfg.vocab_size, (N_REQUESTS, PROMPT_LEN))
    results: list = [None] * N_REQUESTS

    def client(i):
        body = {"model": cfg.name, "prompt": prompts[i].tolist(),
                "max_tokens": MAX_TOKENS, "stream": i % 2 == 0}
        results[i] = _post(http.address, body)

    with http:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(N_REQUESTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    wall = time.perf_counter() - t0
    summary = eng.summary()
    info("serve", arch=SERVE_ARCH, backend=summary["backend"],
         paged_impl=summary["paged_impl"], requests=N_REQUESTS,
         decode_steps=summary["decode_steps"], wall_s=round(wall, 3),
         **clock.since(mark))
    for i, res in enumerate(results):
        check(res is not None, f"request {i} got no answer")
        out, ttft, e2e = res
        n_tok = len(out["choices"][0]["token_ids"])
        info("serve", request=i, stream=i % 2 == 0, tokens=n_tok,
             ttft_s=None if ttft is None else round(ttft, 4),
             e2e_s=round(e2e, 4))
        check(n_tok == MAX_TOKENS, f"request {i}: {n_tok} tokens")
    check(summary["backend"] == "paged", summary["backend"])
    check(summary["paged_impl"] == EXPECTED_PAGED_IMPL, summary["paged_impl"])
    check_decode_kernel(cfg, eng)


def check_decode_kernel(cfg, eng):
    """One teacher-forced decode step on seeded random pages shaped like
    the engine's pool (its own pages hold only the few blocks the requests
    used).

    The engine's own decode program, the compiled object its ticks called,
    must contain a Mosaic call.  That program returns only the greedy
    token, so the logits come from ``api.paged_decode_step``, the function
    it wraps, four ways: the Pallas and jnp paths, each in the engine's
    bf16 and in f32 at HIGHEST matmul precision.  f32 jnp is the truth.
    f32 Pallas must match it within F32_LOGIT_TOL.  In bf16 the two paths
    round differently and 28 layers amplify each ulp, so bf16 Pallas is
    held to be no further from the truth than bf16 jnp, within
    BF16_ERR_RATIO.  The engine program's tokens must equal the bf16
    Pallas argmax on every lane whose top-two gap exceeds the largest
    bf16 Pallas-vs-jnp difference: rounding cannot flip those lanes."""
    backend = eng.backend
    leaves, tree = jax.tree.flatten(backend.pool.pages)
    keys = jax.random.split(jax.random.PRNGKey(SEED + 1), len(leaves))
    pages = tree.unflatten([jax.random.normal(k, a.shape, a.dtype)
                            for k, a in zip(keys, leaves)])
    n, width = backend.capacity, backend.max_blocks
    rng = np.random.default_rng(SEED + 1)
    ids = rng.permutation(np.arange(1, backend.pool.n_blocks))
    tables = jnp.asarray(ids[: n * width].reshape(n, width), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, MAX_SEQ - 1, n), jnp.int32)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (n, 1)), jnp.int32)
    args = (eng.params, pages, tables, lengths, tokens)

    engine_step = backend._decode.lower(*args).compile()
    check(KERNEL_MARKER in engine_step.as_text(),
          f"decode step has no {KERNEL_MARKER}: the kernel did not compile in")
    # the program donates its pages: hand it a copy
    engine_tokens = np.asarray(engine_step(
        eng.params, jax.tree.map(jnp.copy, pages), tables, lengths,
        tokens)[0])[:, 0]

    def logits(impl, dtype):
        c = cfg.replace(dtype=dtype)
        out = jax.jit(lambda p, pg, t, le, tok: api.paged_decode_step(
            c, p, pg, t, le, tok, impl=impl)[0])(*args)
        return np.asarray(out, np.float32)[:, -1, :]

    kernel_bf16 = logits(backend.paged_impl, cfg.dtype)
    ref_bf16 = logits("jnp", cfg.dtype)
    with jax.default_matmul_precision("highest"):
        kernel = logits(backend.paged_impl, jnp.float32)
        truth = logits("jnp", jnp.float32)

    def rms_err(x):
        return float(np.sqrt(np.mean(np.square(x - truth))))

    bf16_gap = float(np.max(np.abs(kernel_bf16 - ref_bf16)))
    top2 = np.sort(kernel_bf16, axis=-1)[:, -2:]
    clear = top2[:, 1] - top2[:, 0] > bf16_gap
    agree = engine_tokens == kernel_bf16.argmax(-1)
    info("serve", f32_max_abs_diff=float(np.max(np.abs(kernel - truth))),
         f32_tol=F32_LOGIT_TOL, logit_peak=float(np.max(np.abs(truth))),
         bf16_pallas_rms_err=rms_err(kernel_bf16),
         bf16_jnp_rms_err=rms_err(ref_bf16),
         bf16_pallas_max_err=float(np.max(np.abs(kernel_bf16 - truth))),
         bf16_jnp_max_err=float(np.max(np.abs(ref_bf16 - truth))),
         bf16_pallas_vs_jnp=bf16_gap, engine_lanes_checked=int(clear.sum()),
         engine_lanes_agree=int(agree.sum()))
    np.testing.assert_allclose(kernel, truth, rtol=F32_LOGIT_TOL,
                               atol=F32_LOGIT_TOL)
    check(rms_err(kernel_bf16) <= BF16_ERR_RATIO * rms_err(ref_bf16),
          "bf16 Pallas logits are further from the f32 truth than bf16 jnp")
    check(bool(agree[clear].all()),
          f"engine decode tokens {engine_tokens} differ from the Pallas "
          f"argmax on lanes {np.flatnonzero(clear & ~agree)}")


def four_chip_phase(clock: CompileClock):
    """The SPMD trainer on the auto mesh over all four chips against the
    same job on a 1x1 mesh over one of them, in one process."""
    cfg = get_config(SPMD_ARCH)
    devices = jax.devices()
    session = hydra.Session(profile=None)
    common = dict(steps=SPMD_STEPS, batch=SPMD_BATCH, seq=SPMD_SEQ,
                  seed=SEED, log_every=1)
    sharded = session.submit(hydra.SpmdTrainJob(cfg, mesh="auto", **common))
    single = session.submit(hydra.SpmdTrainJob(
        cfg, mesh=make_mesh((1, 1), ("data", "model"),
                            devices=devices[:1]), **common))
    mark = clock.mark()
    t0 = time.perf_counter()
    report = session.run()
    wall = time.perf_counter() - t0
    res_s, res_1 = report.spmd[sharded], report.spmd[single]
    loss_s = [h["loss"] for h in res_s["history"]]
    loss_1 = [h["loss"] for h in res_1["history"]]
    gnorm_s = [h["grad_norm"] for h in res_s["history"]]
    gnorm_1 = [h["grad_norm"] for h in res_1["history"]]
    info("four-chips", arch=SPMD_ARCH, params=res_s["params"],
         sharded_devices=res_s["param_devices"],
         single_devices=res_1["param_devices"], wall_s=round(wall, 3),
         **clock.since(mark))
    info("four-chips", sharded_losses=loss_s, single_losses=loss_1,
         max_abs_diff=float(np.max(np.abs(np.subtract(loss_s, loss_1)))),
         tol=SPMD_LOSS_TOL)
    info("four-chips", sharded_gnorms=gnorm_s, single_gnorms=gnorm_1,
         max_rel_diff=float(np.max(np.abs(np.subtract(gnorm_s, gnorm_1))
                                   / np.asarray(gnorm_1))),
         rtol=SPMD_GNORM_RTOL)
    check(res_s["param_devices"] == len(devices) == 4,
          f"2x2 mesh params span {res_s['param_devices']} devices")
    check(res_1["param_devices"] == 1,
          f"1x1 mesh params span {res_1['param_devices']} devices")
    check(len(loss_s) == len(loss_1) == SPMD_STEPS
          and all(math.isfinite(x) for x in loss_s + loss_1),
          f"losses {loss_s} vs {loss_1}")
    # the bound must be finer than what one update moves the gradient
    # norm, or it could not tell a skipped update from a taken one
    moves = np.abs(np.diff(gnorm_1)) / np.asarray(gnorm_1[:-1])
    check(bool(np.all(moves > SPMD_GNORM_RTOL)),
          f"1x1 gradient norms {gnorm_1} move less than {SPMD_GNORM_RTOL}")
    np.testing.assert_allclose(loss_s, loss_1, rtol=0, atol=SPMD_LOSS_TOL)
    np.testing.assert_allclose(gnorm_s, gnorm_1, rtol=SPMD_GNORM_RTOL,
                               atol=0)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip SPMD comparison")
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()
    n_chips = 4 if args.four_chips else 1
    dev = device_phase(n_chips)
    clock = CompileClock()
    info("setup", compile_cache=cache_dir)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(clock)
    else:
        train_phase(dev, clock)
        gc.collect()    # free the train session's device buffers first
        serve_phase(clock)
    info("total", wall_s=round(time.perf_counter() - t0, 3),
         compile_s=round(clock.seconds, 3), cache_hits=clock.cache_hits)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
