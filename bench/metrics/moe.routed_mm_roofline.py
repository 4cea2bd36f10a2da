"""The held experts' grouped matmuls against their roofline, in %: over
the traced window, the least time the chip could take for them (the larger
of their FLOPs over the bf16 peak and their bytes over HBM bandwidth) over
their device time.  Device time and the number of executions are the trace's
ops named in the configuration's ``programs.routed_mm``.  Every execution
(forward gate, up and down, their recomputations, both backward products)
runs over one routed pass's rows: the window's forward rows over its
forward passes (the counters ``expert_rows`` and ``expert_passes``).  FLOPs
and bytes: ``routed_mm_cost`` of the configuration's reference module."""

from peaks import peak


def read(ctx):
    if ctx.trace is None:
        return None
    c = ctx.outcome.counters
    names = ctx.cell.config["programs"].get("routed_mm", [])
    busy = sum(ctx.trace.op_s.get(n, 0.0) for n in names)
    calls = sum(ctx.trace.op_count.get(n, 0) for n in names)
    if busy <= 0 or not calls or not c.get("expert_passes"):
        return None
    flops, nbytes = ctx.cell.reference.routed_mm_cost(
        ctx.cell.config["model"], sum(c["expert_rows"]) / c["expert_passes"],
        calls / ctx.trace.chips)
    p = peak(ctx.device_kind)
    least = max(flops / p["bf16_flops_per_s"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least / busy
