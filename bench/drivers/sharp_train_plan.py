"""Driver of SHARP training cells of any family: one spilled fine-tune
through ``hydra.Session`` -> ``TrainJob`` -> the SHARP executor, its leaves
named from the shard plan's segment refs.

Runs as ``sharp_train`` does (set-up, ``check_steps`` steps the check
reads, ``warm_steps`` more, then whole steps until ``--seconds`` have
passed, one ``Session.run(max_units=2 * shards)`` a step) and honours the
same controls.  The differences:

- a leaf's name is its segment's ref: ``<group>.<path>`` for a whole group
  (``embed.table``, ``head.lm_head``), ``<key>.<i>.<path>`` for layer i of
  a stacked group (``layers.3.moe.w_gate``), so any family's tree works;
- the reference trainer is the configuration's own (``configs/<config>.py``
  ``train``), with the signature of ``reftrain.train``;
- the window's program counters (``TransferStats.counters``, such as the
  rows the held experts computed) are read beside ``promoted_bytes``;
- where the reference has a ``precompile``, its programs compile in another
  thread once the weights are drawn, while the program sets up; the run
  waits for them before the window, and that wait counts with the check's
  readings, not with ``setup_s``.
"""

from __future__ import annotations

import gc
import time
from concurrent.futures import ThreadPoolExecutor
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import reftrain
from harness import (Outcome, host_peak_rss_gib, info, process_age_s,
                     stop_compile_cache_writes)
from sharp_train import Feed, _check_optimizer
from traffic.generate import train_batch

_layer_norms = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(
    jnp.square(a - b), axis=tuple(range(1, a.ndim)))))
_norms = jax.jit(lambda a: jnp.sqrt(jnp.sum(
    jnp.square(a), axis=tuple(range(1, a.ndim)))))


def _leaves(tree):
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        yield ".".join(str(getattr(k, "key", k)) for k in path), leaf


def _stacked(ref, leaf):
    """A leaf as (layers, ...): a slice of a stacked group already is."""
    a = jnp.asarray(leaf)
    return a if ref[0] == "stack_slice" else a[None]


def _names(ref, path: str, n: int) -> list:
    if ref[0] == "stack_slice":
        return [f"{ref[1]}.{ref[2] + i}.{path}" for i in range(n)]
    return [".".join(ref) + f".{path}"]


def grad_norms(store, plan, partition, b1: float) -> dict:
    """The first gradient as the optimizer got it, leaf by leaf: the Adam
    first moment after one step is (1 - b1) times it."""
    out = {}
    for shard in partition.shards:
        mu = store.opt[shard.index]["mu"]
        for k, i in enumerate(range(shard.seg_lo, shard.seg_hi)):
            ref = plan.segments[i].param_ref
            if ref is None or mu[k] is None:
                continue
            for path, leaf in _leaves(mu[k]):
                vals = np.asarray(_norms(_stacked(ref, leaf)))
                out.update(zip(_names(ref, path, len(vals)),
                               (float(v) / (1 - b1) for v in vals)))
    return out


def change_norms(store, plan, params0) -> dict:
    """Leaf by leaf, the norm of (host-store weights - initial weights)."""
    from repro.core import shard_graph as sg
    out = {}
    for seg in plan.segments:
        ref = seg.param_ref
        if ref is None:
            continue
        p0 = dict(_leaves(sg.resolve_ref(params0, ref)))
        for path, leaf in _leaves(sg.resolve_ref(store.params, ref)):
            vals = np.asarray(_layer_norms(_stacked(ref, leaf),
                                           _stacked(ref, p0[path])))
            out.update(zip(_names(ref, path, len(vals)), map(float, vals)))
    return out


def _counters(stats) -> dict:
    return {k: np.array(v) for k, v in stats.counters.items()}


def run(ctx) -> Outcome:
    import hydra
    from repro.core import shard_graph as sg
    from repro.models import api

    cell, seed = ctx.cell, ctx.seed
    spec, mix, job = cell.config, cell.traffic, cell.config["job"]
    cfg = cell.arch_config()
    m = spec["model"]
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
    # from here the reference's programs compile beside the program's
    # set-up (after the weights, whose own compile they would slow)
    precompile = getattr(cell.reference, "precompile", None)
    ref_compile = precompile and ThreadPoolExecutor(1).submit(
        precompile, m, job, mix["batch"], mix["seq"])
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
    plan = ex.plan
    info("setup", weights_and_store_s=round(time.perf_counter() - t, 3),
         shards=shards, budget_bytes=budget,
         segments=[[plan.segments[i].name
                    for i in range(s.seg_lo, s.seg_hi)]
                   for s in ex.partition.shards],
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
            prog["grad_norm"] = grad_norms(ex.store, plan, ex.partition,
                                           job["b1"])
        if k == n_check - 1:
            params0 = sg.prepare_host_params(cfg,
                                             init(jax.random.PRNGKey(seed)))
            prog["change_norm"] = change_norms(ex.store, plan, params0)
            del params0
        readings_s += time.perf_counter() - r0
    prog["losses"] = list(ex.losses[:n_check])
    for k in range(n_check, n_check + job["warm_steps"]):
        info("setup", step=k, step_s=round(step(k), 3))
    n_setup = n_check + job["warm_steps"]
    if ref_compile:
        r0 = time.perf_counter()
        ref_compile.result()
        wait_s = time.perf_counter() - r0
        readings_s += wait_s
        info("setup", reference_compile_wait_s=round(wait_s, 3))
    setup_s = process_age_s() - readings_s
    info("setup", setup_s=round(setup_s, 3), readings_s=round(readings_s, 3),
         losses=prog["losses"], host_peak_rss_gib=round(host_peak_rss_gib(),
                                                        2))

    # -- the window: whole steps until --seconds have passed --------------
    mark = ctx.clock.mark()
    promoted0, counts0 = stats.promoted_bytes, _counters(stats)
    ctx.tracer.start()
    t0 = time.perf_counter()
    step_s = []
    while not step_s or time.perf_counter() - t0 < ctx.seconds:
        step_s.append(step(n_setup + len(step_s)))
    t1 = time.perf_counter()
    ctx.tracer.stop()
    window_compiles = ctx.clock.since(mark)
    tokens = len(step_s) * mix["batch"] * mix["seq"]
    counts = {k: v - counts0.get(k, 0) for k, v in _counters(stats).items()}
    counters = {"tokens": tokens, "steps": len(step_s), "window_s": t1 - t0,
                "promoted_bytes": stats.promoted_bytes - promoted0,
                "memory_peak_bytes": (dev.memory_stats() or {}).get(
                    "peak_bytes_in_use", 0),
                "train_flops_per_token": cell.reference.train_flops_per_token(
                    m, mix["seq"]),
                **{k: v.tolist() for k, v in counts.items()}}
    e2e = {"train_tokens_per_s": tokens / (t1 - t0), "setup_s": setup_s}
    info("window", steps=len(step_s), step_s=[round(s, 3) for s in step_s],
         tokens=tokens, **window_compiles,
         promoted_bytes=counters["promoted_bytes"],
         peak_bytes_in_use=counters["memory_peak_bytes"],
         host_peak_rss_gib=round(host_peak_rss_gib(), 2),
         **{k: v.tolist() for k, v in counts.items()})

    # -- the reference, once the program's state is freed ------------------
    del ex, session, feed, tj
    gc.collect()
    stop_compile_cache_writes()

    def batches(k):
        return train_batch(mix, seed, k, m["vocab_size"])
    r0 = time.perf_counter()
    ref = cell.reference.train(m, job, seed, batches, n_check)
    cmp = reftrain.compare(prog, ref)
    info("check", reference_s=round(time.perf_counter() - r0, 3),
         reference_step_s=ref.get("step_s"),
         program_losses=prog["losses"], reference_losses=ref["losses"],
         grad_leaf=cmp["grad_leaf"], change_leaf=cmp["change_leaf"],
         leaves=cmp["leaves"], leaves_moved=cmp["leaves_moved"],
         host_peak_rss_gib=round(host_peak_rss_gib(), 2))
    counters["control"] = {}
    for name in ctx.controls:
        # a precision below the stated one, or half of each batch left out
        kw = ({"rows": mix["batch"] // 2} if name == "half_batch"
              else {"precision": name})
        ctrl = cell.reference.train(m, job, seed, batches, n_check, **kw)
        got = reftrain.compare(ctrl, ref)
        counters["control"][name] = got
        info("control", control=name, **got, losses=ctrl["losses"])
    limits = spec["checks"]
    checks = {k: (cmp[k], limits[k]) for k in
              ("loss_gap", "grad_gap", "change_gap")}
    return Outcome(e2e=e2e, counters=counters, attempted=len(step_s),
                   failed=0, checks=checks)
