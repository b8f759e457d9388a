"""Share of the window spent assembling what a call returns: the
program's ``engine.results`` spans (the per-trial results, telemetry
and the batch result) over the window (host clock)."""
from bench.metrics_util import span_share


def read(ctx):
    return span_share(ctx, "engine.results")
