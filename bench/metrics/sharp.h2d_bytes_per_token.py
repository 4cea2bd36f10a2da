"""Bytes SHARP promoted host -> device over the window (the session
ledger's ``promoted_bytes``), per token trained."""


def read(ctx):
    c = ctx.outcome.counters
    if not c.get("tokens"):
        return None
    return c["promoted_bytes"] / c["tokens"]
