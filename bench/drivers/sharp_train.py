"""Driver of SHARP training cells: one spilled fine-tune through
``hydra.Session`` -> ``TrainJob`` -> the SHARP executor.

Set-up makes the weights on the device in one jitted call of the program's
own initialiser, builds the host store (``Session.train_execs``), then drives the
job through its first ``check_steps`` optimizer steps with the same call
the window makes, ``Session.run(max_units=2 * shards)``, one step per call
(``run`` rebuilds the minibatch queue, so a call never stops mid-step),
and ``warm_steps`` more, since a process's first steps are slower than
the rest.  The window then runs whole steps until ``--seconds`` have
passed; a step ends when its last demotion has returned to the host.

Correctness: the program's readings after the first steps, taken from its
host store (the first gradient from the Adam first moment, the weights'
change against the initial weights made again by the same jitted call),
against the plain reference (``reftrain``) computed once the window has
closed and the program's state is freed.
"""

from __future__ import annotations

import gc
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import reftrain
from harness import (Outcome, host_peak_rss_gib, info, process_age_s,
                     stop_compile_cache_writes)
from traffic.generate import train_batch


class Feed:
    """The job's dataloader: step ``step``'s batch for every draw, so the
    batch a step trains on is fixed by the step, whatever the executor
    draws ahead."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, seed, vocab
        self.step = 0

    def __iter__(self):
        return self

    def __next__(self) -> dict:
        return train_batch(self.mix, self.seed, self.step, self.vocab)


def _check_optimizer(ocfg, job: dict) -> None:
    want = {k: job[k] for k in ("lr", "b1", "b2", "eps", "weight_decay",
                                "grad_clip", "schedule")}
    got = {k: getattr(ocfg, k) for k in want}
    if got != want or ocfg.kind != job["optimizer"]:
        raise RuntimeError(f"the program's optimizer {ocfg} is not the "
                           f"configuration's {job}")


_layer_norms = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(
    jnp.square(a - b), axis=tuple(range(1, a.ndim)))))
_norm = jax.jit(lambda a: jnp.sqrt(jnp.sum(jnp.square(a))))


def _names(prefix, tree):
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        yield ".".join([prefix] + [str(getattr(k, "key", k))
                                   for k in path]), leaf


def grad_norms(store, plan, partition, b1: float) -> dict:
    """The first gradient as the optimizer got it, leaf by leaf: the Adam
    first moment after one step is (1 - b1) times it."""
    out = {}
    for shard in partition.shards:
        mu = store.opt[shard.index]["mu"]
        for k, i in enumerate(range(shard.seg_lo, shard.seg_hi)):
            seg = plan.segments[i].name
            if mu[k] is None:
                continue
            prefix = (f"layers.{int(seg[len('layer'):])}"
                      if seg.startswith("layer") else "final_norm")
            for name, leaf in _names(prefix, mu[k]):
                out[name] = float(_norm(jnp.asarray(leaf))) / (1 - b1)
    for name, leaf in _names("embed", store.shared_opt["embed"]["mu"]):
        out[name] = float(_norm(jnp.asarray(leaf))) / (1 - b1)
    return out


def change_norms(store, params0) -> dict:
    """Leaf by leaf, the norm of (host-store weights - initial weights)."""
    out = {}
    p0s = dict(_names("layers", params0["layers"]))
    for name, leaf in _names("layers", store.params["layers"]):
        per_layer = np.asarray(_layer_norms(jnp.asarray(leaf), p0s[name]))
        for i, v in enumerate(per_layer):
            head, tail = name.split(".", 1)
            out[f"{head}.{i}.{tail}"] = float(v)
    for group in ("final_norm", "embed"):
        p0 = dict(_names(group, params0[group]))
        for name, leaf in _names(group, store.params[group]):
            out[name] = float(_norm(jnp.asarray(leaf) - p0[name]))
    return out


def run(ctx) -> Outcome:
    import hydra
    from repro.models import api

    cell, seed = ctx.cell, ctx.seed
    spec, mix, job = cell.config, cell.traffic, cell.config["job"]
    cfg = cell.arch_config()
    dev = jax.devices()[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit",
                                          job.get("budget_bytes_off_chip"))
    budget = int(limit * job["device_budget_fraction"])
    session = hydra.Session(hydra.HydraConfig(
        n_devices=1, device_budget_bytes=budget, seed=seed % 2**31,
        pilot=job["pilot"]), profile=None)
    feed = Feed(mix, seed, cfg.vocab_size)
    init = jax.jit(partial(api.init_params, cfg))
    t = time.perf_counter()
    params = init(jax.random.PRNGKey(seed))
    tj = hydra.TrainJob(cfg, dataloader=feed, lr=job["lr"], epochs=1,
                        steps_per_epoch=10**6, optimizer=job["optimizer"],
                        params=params, seed=seed % 2**31, batch=mix["batch"],
                        seq=mix["seq"])
    _check_optimizer(tj.opt_config(), job)
    session.submit(tj)
    (ex,) = session.train_execs            # builds the host store
    tj.params = None
    del params
    shards = len(ex.partition.shards)
    info("setup", weights_and_store_s=round(time.perf_counter() - t, 3),
         shards=shards, budget_bytes=budget,
         host_peak_rss_gib=round(host_peak_rss_gib(), 2))
    stats = session.devices[0].stats

    def step(k: int) -> float:
        feed.step = k
        t0 = time.perf_counter()
        report = session.run(max_units=2 * shards)
        if report.train.units_executed != 2 * shards:
            raise RuntimeError(f"step {k} ran {report.train.units_executed}"
                               f" units, not {2 * shards}")
        return time.perf_counter() - t0

    n_check = job["check_steps"]
    readings_s = 0.0
    prog = {}
    for k in range(n_check):
        mark = ctx.clock.mark()
        dt = step(k)
        info("setup", step=k, step_s=round(dt, 3), **ctx.clock.since(mark))
        r0 = time.perf_counter()
        if k == 0:
            prog["grad_norm"] = grad_norms(ex.store, ex.plan, ex.partition,
                                           job["b1"])
        if k == n_check - 1:
            params0 = init(jax.random.PRNGKey(seed))
            prog["change_norm"] = change_norms(ex.store, params0)
            del params0
        readings_s += time.perf_counter() - r0
    prog["losses"] = list(ex.losses[:n_check])
    # a process's first steps are its slowest; the window starts after them
    for k in range(n_check, n_check + job["warm_steps"]):
        info("setup", step=k, step_s=round(step(k), 3))
    n_setup = n_check + job["warm_steps"]
    setup_s = process_age_s() - readings_s
    info("setup", setup_s=round(setup_s, 3), readings_s=round(readings_s, 3),
         losses=prog["losses"], host_peak_rss_gib=round(host_peak_rss_gib(),
                                                        2))

    # -- the window: whole steps until --seconds have passed --------------
    mark = ctx.clock.mark()
    promoted0 = stats.promoted_bytes
    ctx.tracer.start()
    t0 = time.perf_counter()
    step_s = []
    while not step_s or time.perf_counter() - t0 < ctx.seconds:
        step_s.append(step(n_setup + len(step_s)))
    t1 = time.perf_counter()
    ctx.tracer.stop()
    window_compiles = ctx.clock.since(mark)
    tokens = len(step_s) * mix["batch"] * mix["seq"]
    counters = {"tokens": tokens, "steps": len(step_s), "window_s": t1 - t0,
                "promoted_bytes": stats.promoted_bytes - promoted0,
                "memory_peak_bytes": (dev.memory_stats() or {}).get(
                    "peak_bytes_in_use", 0),
                "train_flops_per_token": cell.reference.train_flops_per_token(
                    spec["model"], mix["seq"])}
    e2e = {"train_tokens_per_s": tokens / (t1 - t0), "setup_s": setup_s}
    info("window", steps=len(step_s), step_s=[round(s, 3) for s in step_s],
         tokens=tokens, **window_compiles,
         promoted_bytes=counters["promoted_bytes"],
         peak_bytes_in_use=counters["memory_peak_bytes"],
         host_peak_rss_gib=round(host_peak_rss_gib(), 2))

    # -- the reference, once the program's state is freed ------------------
    del ex, session, feed, tj
    gc.collect()
    stop_compile_cache_writes()
    r0 = time.perf_counter()
    ref = reftrain.train(cell.reference, spec["model"], job, seed,
                         lambda k: train_batch(mix, seed, k,
                                               spec["model"]["vocab_size"]),
                         n_check)
    cmp = reftrain.compare(prog, ref)
    info("check", reference_s=round(time.perf_counter() - r0, 3),
         program_losses=prog["losses"], reference_losses=ref["losses"],
         grad_leaf=cmp["grad_leaf"], change_leaf=cmp["change_leaf"],
         leaves=cmp["leaves"], leaves_moved=cmp["leaves_moved"])
    counters["control"] = {}
    for name in ctx.controls:
        # a precision below the stated one, or half of each batch left out
        kw = ({"rows": mix["batch"] // 2} if name == "half_batch"
              else {"precision": name})
        ctrl = reftrain.train(cell.reference, spec["model"], job, seed,
                              lambda k: train_batch(
                                  mix, seed, k, spec["model"]["vocab_size"]),
                              n_check, **kw)
        got = reftrain.compare(ctrl, ref)
        counters["control"][name] = got
        info("control", control=name, **got, losses=ctrl["losses"])
    limits = spec["checks"]
    checks = {k: (cmp[k], limits[k]) for k in
              ("loss_gap", "grad_gap", "change_gap")}
    return Outcome(e2e=e2e, counters=counters, attempted=len(step_s),
                   failed=0, checks=checks)
