"""Share of the traced window in which no operation ran on the chip:
1 - (union of the device's operation intervals) / window."""
from bench import tracereduce as tr


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace
    busy = [tr.busy_ns(ev, t["w0"], t["w1"]) for ev in t["devices"].values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (t["w1"] - t["w0"]))
