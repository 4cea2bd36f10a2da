"""Share of the traced window in which no XLA op ran on the chip (host
<->device transfers do not count as busy), in %."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share()
