"""Share of the traced window in which the chip is idle and the host is
under none of the program's spans: the idle time that no span names.

The program's spans are those of its ring buffer, which the harness
moves onto the trace's clock (``ctx.trace["spans"]``); the harness's own
``sweep.call`` annotations cover every call whole and do not count.
Averaged over the cell's chips."""
from bench import tracereduce as tr

CALL = "sweep.call"


def overlap_ns(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    (start, end) intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def untraced_ns(events, spans, w0, w1) -> float:
    """Idle time of one chip inside [w0, w1] under no span of ``spans``
    ([start, dur, name, ...])."""
    idle = tr.gaps(events, w0, w1)
    covered = tr.clip(tr.union((s, s + d) for s, d, *_ in spans), w0, w1)
    return sum(e - s for s, e in idle) - overlap_ns(idle, covered)


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace
    spans = [s for s in t["spans"] if s[2] != CALL]
    if not spans:
        return None
    idle = [untraced_ns(ev, spans, t["w0"], t["w1"])
            for ev in t["devices"].values()]
    return 100.0 * sum(idle) / len(idle) / (t["w1"] - t["w0"])
