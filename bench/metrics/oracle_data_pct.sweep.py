"""Share of the window the float64 host oracle spends on arithmetic over
the problem's data: the program's ``numpy.data`` spans (residuals and
losses, shard gradients of both phases, aggregate and update) over the
window (host clock).  The rest of the oracle's span is its control."""
from bench.metrics_util import span_share


def read(ctx):
    return span_share(ctx, "numpy.data")
