"""The gram-plane precompute kernel's share of its roofline: the least
time the chip could take for what the calls in the window needed (one
read of the extended data matrix, G = R R^T and one CountSketch table a
step; ``counts.gram_factors_cost``), over the device time of the
kernel's events in the trace (the Pallas call of the ``gram_factors``
module).  Nothing to read where the window ran no such kernel."""
from bench import counts
from bench.metrics_util import kernel_seconds


def read(ctx):
    if ctx.trace is None:
        return None
    seconds = kernel_seconds(ctx.trace, "gram_factors")
    if not seconds:
        return None
    prob = ctx.config["problem"]
    cost = counts.gram_factors_cost(prob["n_data"] + 2, prob["d"],
                                    ctx.traffic["steps"])
    least, _ = counts.roofline_seconds(cost, ctx.peaks)
    calls = sum(1 for r in ctx.records if not r["error"])
    return 100.0 * calls * least / seconds
