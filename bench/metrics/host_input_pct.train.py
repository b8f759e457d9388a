"""Share of the timed calls the trainer's host spends on a step's input:
the program's ``train.batch`` spans (the step's global batch) and
``train.put`` spans (the worker shards sliced for the step's assignment
and copied to the devices, enqueued) over Σ of the calls' seconds (host
clock)."""
from bench import calls


def read(ctx):
    return calls.span_share(ctx, {"train.batch", "train.put"})
