"""The timed calls of a window, for the readers whose denominator is the
calls' own time rather than the window's: what the driver does between
calls (the trainer cell's host copies kept for its check) is in neither
the numerator nor the denominator.

``ctx.records`` holds each call's ``t0`` and ``t1`` on the host's perf
counter; the trace's window starts at the first call's ``t0`` moved onto
the trace's clock, which gives the offset between the two clocks.
"""
from __future__ import annotations

from bench import tracereduce as tr


def seconds(ctx) -> float:
    """Σ (t1 - t0) over the window's calls."""
    return sum(r["t1"] - r["t0"] for r in ctx.records) / 1e9


def span_share(ctx, names) -> float | None:
    """Percent of the calls' time covered by the program's spans of the
    given names (each inside a call), or None where there are none."""
    spans = [s for s in ctx.program_spans if s["name"] in names]
    if not spans:
        return None
    return 100.0 * sum(s["dur_ns"] for s in spans) / 1e9 / seconds(ctx)


def intervals(ctx) -> list[tuple[float, float]]:
    """Each call's [t0, t1] on the trace's clock."""
    offset = ctx.trace["w0"] - ctx.records[0]["t0"]
    return [(r["t0"] + offset, r["t1"] + offset) for r in ctx.records]


def overlap_ns(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    (start, end) intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def busy_ns(ctx, events) -> float:
    """Time inside the calls in which at least one of ``events`` ran."""
    return overlap_ns(tr.union((s, s + d) for s, d, *_ in events),
                      intervals(ctx))
