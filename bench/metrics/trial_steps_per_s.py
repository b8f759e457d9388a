"""Trial-steps per second of an engine sweep: the trials times steps of
every call completed in the window, over the wall time of those whole
calls (host clock).  A call's time includes everything a user waits for:
problem build, schedule build, transfers, the scan and the results."""


def read(ctx):
    done = [r for r in ctx.records if not r["error"]]
    if not done:
        return None
    return sum(r["work"] for r in done) / (
        sum(r["t1"] - r["t0"] for r in done) / 1e9)
