"""Share of the timed calls spent in the trainer's fast steps: the
program's ``train.fast`` spans (each from the step's worker batches to
its loss read on the host) over Σ of the calls' seconds (host clock),
so that the host copies the driver makes between calls are left out."""
from bench import calls


def read(ctx):
    return calls.span_share(ctx, {"train.fast"})
