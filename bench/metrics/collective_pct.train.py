"""Share of the timed calls in which a chip runs a collective: the union
of the intervals of its all-reduce, all-gather and reduce-scatter
operations inside the calls over Σ of the calls' time, averaged over the
cell's chips.  Nothing to read where the window ran none.

An operation is known by its name in the trace: XLA's (``all-reduce.3``,
``all-gather-start.1``) or the JAX primitive's a lowered collective
keeps (``psum.95`` is the gradient's all-reduce on a TPU v5e)."""
from bench import calls

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all_reduce",
               "all_gather", "reduce_scatter", "psum")


def read(ctx):
    if ctx.trace is None:
        return None
    shares = [calls.busy_ns(ctx, [e for e in ev
                                  if e[2].startswith(COLLECTIVES)])
              for ev in ctx.trace["devices"].values()]
    if not any(shares):
        return None
    return 100.0 * sum(shares) / len(shares) / 1e9 / calls.seconds(ctx)
