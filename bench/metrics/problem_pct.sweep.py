"""Share of the window spent building and staging the problem on the
host: the program's ``engine.make_problem`` spans (the problems of the
call) and ``engine.stage_problem`` spans (per-trial statics, scan
inputs, data rows, the gram precompute's dispatch, the host G and the
placement of the chunk-invariant operands) over the window (host
clock)."""
from bench.metrics_util import span_share

SPANS = ("engine.make_problem", "engine.stage_problem")


def read(ctx):
    shares = [x for x in (span_share(ctx, n) for n in SPANS) if x is not None]
    return sum(shares) if shares else None
