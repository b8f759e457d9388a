"""Share of the timed calls in which no operation ran on a chip:
1 - (union of the chip's operation intervals inside the calls) / Σ of
the calls' time, averaged over the cell's chips (one worker a chip).
The host copies the driver makes between calls are left out."""
from bench import calls


def read(ctx):
    if ctx.trace is None:
        return None
    busy = [calls.busy_ns(ctx, ev) for ev in ctx.trace["devices"].values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / 1e9 / calls.seconds(ctx))
