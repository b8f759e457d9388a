"""Share of the timed calls spent in the trainer's check steps: the
program's ``train.check`` spans (each from the step's worker batches,
replicated over groups of f + 1 workers, to its loss and fault verdict
read on the host) over Σ of the calls' seconds (host clock)."""
from bench import calls


def read(ctx):
    return calls.span_share(ctx, {"train.check"})
