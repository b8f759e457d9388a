"""Share of the window spent moving a call's arrays between host and
chip: the program's ``pipeline.stage`` spans (host to device, per
chunk) and ``pipeline.fetch`` spans (device to host, with the float64
copies of the iterates and losses) over the window (host clock)."""
from bench.metrics_util import span_share

SPANS = ("pipeline.stage", "pipeline.fetch")


def read(ctx):
    shares = [x for x in (span_share(ctx, n) for n in SPANS) if x is not None]
    return sum(shares) if shares else None
