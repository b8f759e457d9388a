"""Share of the timed calls in which the trainer's host is blocked on
the chips: the program's ``train.sync`` spans, each a wait for a step's
loss (and a check step's fault verdict) to reach the host, over Σ of the
calls' seconds (host clock)."""
from bench import calls


def read(ctx):
    return calls.span_share(ctx, {"train.sync"})
