"""Rows the held experts' grouped matmuls computed in the window's forward
units (the program counter ``TransferStats.counters["expert_rows"]``,
padding included), per token trained.  Dropless routing without padding
reads (MoE layers) x top_k x held / n_experts."""


def read(ctx):
    c = ctx.outcome.counters
    if not c.get("tokens") or "expert_rows" not in c:
        return None
    return sum(c["expert_rows"]) / c["tokens"]
