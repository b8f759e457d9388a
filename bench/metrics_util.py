"""Helpers shared by the per-layer metric readers."""
from __future__ import annotations


def span_share(ctx, name: str) -> float | None:
    """Percent of the window covered by the program's spans ``name``."""
    spans = [s for s in ctx.program_spans if s["name"] == name]
    if not spans:
        return None
    return 100.0 * sum(s["dur_ns"] for s in spans) / 1e9 / ctx.window_s


def kernel_seconds(trace: dict, kernel: str) -> float:
    """Device seconds, averaged over the cell's chips, of the events of
    the Pallas kernel ``kernel``: custom calls named ``<kernel>.<n>``."""
    total = 0.0
    for events in trace["devices"].values():
        total += sum(
            min(s + d, trace["w1"]) - max(s, trace["w0"])
            for s, d, name, kind in events
            if kind == "kernel" and name.rsplit(".", 1)[0] == kernel
            and s + d > trace["w0"] and s < trace["w1"]) / 1e9
    return total / max(1, len(trace["devices"]))
