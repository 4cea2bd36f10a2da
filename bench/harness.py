"""What every cell shares: finding a cell's files by the names in
BENCHMARK.json, the compile clock, tracing, the per-layer metric readers,
and the result line.

A cell names a configuration and a traffic mix.  The configuration's file
(``configs/<config>.json``) holds its sizes, the job or engine settings it
runs with, the limits of its output check, and the name of the driver
that runs it (``drivers/<driver>.py``); its plain reference sits beside it
(``configs/<config>.py``).  The mix is ``traffic/<mix>.json``.  A per-layer
metric is ``metrics/<metric>.py`` with a ``read(ctx)`` that returns a
number, or None when the run holds nothing for it to read.  So a new cell,
configuration, mix or metric is new files and new entries, never an edit.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import resource
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path

from traffic.generate import load as load_mix

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_DIR = HERE / ".traces"
COMPILE_CACHE_DIR = HERE / ".jax_cache"
WINDOW_SPAN = "bench.window"


def info(tag: str, **fields) -> None:
    """One informational line on stdout; never the result line."""
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def process_age_s() -> float:
    """Seconds since this process started (kernel clock ticks)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def keep_compile_cache_in_checkout() -> str:
    """Point JAX's persistent compilation cache at a fixed directory inside
    this checkout, whatever the environment says; call before JAX is
    imported.  The program's ``enable_compile_cache`` then takes it."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(COMPILE_CACHE_DIR)
    return str(COMPILE_CACHE_DIR)


def stop_compile_cache_writes() -> None:
    """Keep whatever compiles from here on out of the persistent cache.
    Where the machine caps the cache's size, JAX evicts the entries used
    least recently, so the reference's programs, compiled after the window,
    would push out the timed path's, and the next run would compile them
    all again."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float("inf"))


def host_peak_rss_gib() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


def load_module(path: Path, name: str | None = None):
    """Import a file by path (names may hold dots and dashes)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    for d in (str(HERE), str(path.parent)):
        if d not in sys.path:
            sys.path.insert(0, d)
    spec = importlib.util.spec_from_file_location(
        name or f"bench_{path.stem.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


@dataclass
class Cell:
    """A workload of BENCHMARK.json with its files resolved by name."""
    name: str
    chips: int
    config: dict                     # configs/<config>.json
    traffic: dict                    # traffic/<mix>.json
    reference: object                # configs/<config>.py
    driver: object                   # drivers/<driver>.py
    end_to_end: list                 # metric entries this cell reports
    per_layer: list

    @classmethod
    def resolve(cls, bench: dict, workload: str) -> "Cell":
        by_name = {w["name"]: w for w in bench["workloads"]}
        if workload not in by_name:
            raise KeyError(f"no workload {workload!r}; have {sorted(by_name)}")
        w = by_name[workload]
        conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
        config = json.loads((ROOT / conf["file"]).read_text())
        traffic = load_mix(w["traffic"])
        reference = load_module(HERE / "configs" / f"{w['config']}.py")
        driver = load_module(HERE / "drivers" / f"{config['driver']}.py")

        def mine(metric):
            return workload in metric.get("workloads", [workload])
        return cls(workload, w["chips"], config, traffic, reference, driver,
                   [m for m in bench["end_to_end"] if mine(m)],
                   [m for m in bench["per_layer"] if mine(m)])

    def arch_config(self):
        """The program's ArchConfig, with every size the file states."""
        import jax.numpy as jnp

        from repro.configs import get_config
        model = dict(self.config["model"])
        for k in ("dtype", "param_dtype"):
            if k in model:
                model[k] = jnp.dtype(model[k]).type
        return get_config(self.config["arch"]).replace(**model)


class CompileClock:
    """Compilations and persistent-cache reads, from JAX's own monitoring
    events (copied from the program's ``chip_smoke.py``)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> tuple:
        return self.compiles, self.cache_hits, self.seconds

    def since(self, mark: tuple) -> dict:
        return {"compiles": self.compiles - mark[0],
                "cache_hits": self.cache_hits - mark[1],
                "compile_s": round(self.seconds - mark[2], 3)}


class Tracer:
    """The device trace of a window, on when ``--trace 1``."""

    def __init__(self, enabled: bool, tag: str):
        self.enabled = enabled
        self.dir = TRACE_DIR / tag
        self._span = None
        self.window_pc: tuple[float, float] | None = None   # perf_counter

    def start(self) -> None:
        if not self.enabled:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self._t0 = time.perf_counter()

    def stop(self) -> None:
        """Close the window; call from the thread that called start."""
        if not self.enabled or self._span is None:
            return
        import jax
        self.window_pc = (self._t0, time.perf_counter())
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self):
        """The reduced trace; the raw files are deleted once read."""
        if not self.enabled:
            return None
        from trace_reduce import reduce_trace
        paths = sorted(self.dir.rglob("*.xplane.pb"))
        if not paths:
            raise RuntimeError(f"no trace written under {self.dir}")
        try:
            return reduce_trace(str(paths[-1]), window_span=WINDOW_SPAN)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclass
class Outcome:
    """What a driver hands back."""
    e2e: dict                        # end-to-end metric name -> value
    counters: dict                   # what the per-layer readers read
    attempted: int
    failed: int
    checks: dict                     # name -> (value, limit)


@dataclass
class Context:
    cell: Cell
    seed: int
    seconds: float
    tracer: Tracer
    clock: CompileClock
    device_kind: str
    controls: tuple = ()             # also read these (check_control)
    trace: object = None
    outcome: Outcome | None = None


def read_per_layer(ctx: Context) -> dict:
    out = {}
    for m in ctx.cell.per_layer:
        reader = load_module(HERE / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def device_summary(n_chips: int) -> dict:
    import jax
    devs = jax.devices()[:n_chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks)}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             controls: tuple = ()) -> dict:
    """Set up, warm up, measure, check; the result line as a dict.  With
    ``controls``, the check's readings of each named control (the
    reference put in the program's place, computed in a lower precision
    or with a fault planted) come back under ``control`` too; the
    benchmark's own runs never ask for them."""
    import jax
    ctx = Context(cell, seed, seconds, Tracer(trace, f"{cell.name}-{seed}"),
                  CompileClock(), jax.devices()[0].device_kind, tuple(controls))
    out = cell.driver.run(ctx)
    ctx.outcome = out
    ctx.trace = ctx.tracer.reduce()
    if ctx.trace is not None:
        top = sorted(ctx.trace.module_s.items(), key=lambda kv: -kv[1])[:12]
        info("trace", window_s=ctx.trace.window_s, busy_s=ctx.trace.busy_s,
             programs=[[k, v, ctx.trace.module_count[k]] for k, v in top],
             transfers=ctx.trace.transfers,
             clock_offset_ns=ctx.trace.clock_offset_ns)
    device = device_summary(cell.chips)
    device["memory_peak_bytes"] = out.counters.get(
        "memory_peak_bytes", device["memory_peak_bytes"])
    correct = out.failed == 0 and all(
        v is not None and lim is not None and v <= lim
        for v, lim in out.checks.values())
    if trace:
        metrics = read_per_layer(ctx)
        device.update(busy_s=ctx.trace.busy_s, window_s=ctx.trace.window_s)
    else:
        metrics = {m["name"]: {"value": out.e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result = {"correct": bool(correct), "attempted": out.attempted,
              "failed": out.failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = ctx.trace.breakdown()
    if controls:
        result["control"] = out.counters["control"]
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in out.checks.items()}
    gc.collect()
    return result
