"""Share of the window in which the host is blocked on the chip: the
program's ``pipeline.wait`` spans, each a wait for one chunk's scan to
finish, over the window (host clock)."""
from bench.metrics_util import span_share


def read(ctx):
    return span_share(ctx, "pipeline.wait")
