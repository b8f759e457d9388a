"""Share of the window spent in the data plane: the program's
``engine.scan`` spans (staging, dispatch, the scan and the drain of the
results; host clock) over the window."""
from bench.metrics_util import span_share


def read(ctx):
    return span_share(ctx, "engine.scan")
