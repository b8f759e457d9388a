"""Share of the window spent building the host control plane: the
program's ``engine.build_schedule`` spans (host clock) over the window."""
from bench.metrics_util import span_share


def read(ctx):
    return span_share(ctx, "engine.build_schedule")
