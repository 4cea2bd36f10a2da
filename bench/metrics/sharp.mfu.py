"""Model FLOPs of the tokens trained in the traced window (6 N + 12 L d s
per token, recompute not counted) over the summed device time of the
shard forward/backward and AdamW programs times the chip's bf16 peak,
in %."""

from peaks import peak


def read(ctx):
    if ctx.trace is None:
        return None
    c = ctx.outcome.counters
    busy = ctx.trace.module_seconds(*ctx.cell.config["programs"]["train_step"])
    if not c.get("tokens") or busy <= 0:
        return None
    flops = c["tokens"] * c["train_flops_per_token"]
    return 100.0 * flops / (busy * peak(ctx.device_kind)["bf16_flops_per_s"])
