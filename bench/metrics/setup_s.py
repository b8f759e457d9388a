"""Seconds from the process's start to the first timed call: imports,
device start, compile or cache load, and the warm-up of the cell's own
shapes (host clock)."""


def read(ctx):
    return ctx.setup_s
