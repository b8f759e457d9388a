"""From a JAX profiler trace to the numbers the benchmark reports.

``load`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes and
keeps two kinds of events, each as ``[start_ns, duration_ns, name,
kind]`` on the trace's own clock:

- every operation on every accelerator (the ``XLA Ops`` line of each
  ``/device:...`` plane), named by its HLO instruction (``gram_factors.1``,
  ``fusion.90``, ``while.3``) and marked ``kernel`` where it is a custom
  call, which is how a Pallas kernel runs;
- the harness's own ``TraceAnnotation`` spans on the host.

Everything else here is arithmetic on those lists, so that it can be
checked on a small recorded trace without a chip.
"""
from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"


def op_name(text: str) -> tuple[str, str]:
    """An event's short name and kind from the HLO text the TPU trace
    gives as its name: ``"%gram_factors.1 = (...) custom-call(...)"`` is
    ("gram_factors.1", "kernel")."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text, ""
    return head.strip().lstrip("%"), ("kernel" if " custom-call(" in rest
                                      else "")


def load(trace_dir: str, host_names: set[str]) -> dict:
    """{"devices": {plane: [[start, dur, name, kind], ...]},
    "host": [[start, dur, name, ""], ...]} from the newest trace under
    ``trace_dir``; host events only where their name is in
    ``host_names``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            events = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    events.append([ev.start_ns, ev.duration_ns,
                                   *op_name(ev.name)])
            out["devices"][plane.name[len(DEVICE_PREFIX):]] = events
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in host_names:
                        out["host"].append([ev.start_ns, ev.duration_ns,
                                            ev.name, ""])
    out["host"].sort()
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, w0: float, w1: float) -> list[tuple[float, float]]:
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if e > w0 and s < w1]


def busy_ns(events, w0: float, w1: float) -> float:
    """Time inside [w0, w1] in which at least one operation ran."""
    spans = clip(union((s, s + d) for s, d, *_ in events), w0, w1)
    return sum(e - s for s, e in spans)


def gaps(events, w0: float, w1: float) -> list[tuple[float, float]]:
    """The idle intervals of one device inside [w0, w1]."""
    out, t = [], w0
    for s, e in clip(union((s, s + d) for s, d, *_ in events), w0, w1):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < w1:
        out.append((t, w1))
    return out


def self_ns(events) -> list[float]:
    """Each event's own time: its duration less that of the events nested
    inside it (the body of a ``while`` or a ``conditional`` runs as
    events of their own within the loop's)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [float(e[1]) for e in events]
    stack: list[int] = []
    for i in order:
        s, d = events[i][0], events[i][1]
        while stack and events[stack[-1]][0] + events[stack[-1]][1] <= s:
            stack.pop()
        if stack and s + d <= events[stack[-1]][0] + events[stack[-1]][1]:
            own[stack[-1]] -= d
        stack.append(i)
    return own


def op_seconds(events, w0: float, w1: float) -> dict[str, float]:
    """Own seconds per operation name, of the events that start inside
    [w0, w1]; numbered instances of one instruction (``fusion.90``) are
    kept apart."""
    out: dict[str, float] = {}
    for e, own in zip(events, self_ns(events)):
        if w0 <= e[0] < w1:
            out[e[2]] = out.get(e[2], 0.0) + own / 1e9
    return out


def innermost(spans, t: float, default: str) -> str:
    """Name of the shortest span [start, dur, name, ...] that covers t."""
    best, best_d = default, None
    for s, d, name, *_ in spans:
        if s <= t <= s + d and (best_d is None or d < best_d):
            best, best_d = name, d
    return best


def top(pairs, n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(pairs, key=lambda kv: -kv[1])[:n]]


def breakdown(devices: dict, spans, w0: float, w1: float) -> dict:
    """The device operations that took the most time (summed over the
    devices, divided by their number), and the longest idle gaps, each
    named by the innermost host span that covers its middle."""
    n = max(1, len(devices))
    ops: dict[str, float] = {}
    idle = []
    for events in devices.values():
        for k, v in op_seconds(events, w0, w1).items():
            ops[k] = ops.get(k, 0.0) + v / n
        for s, e in gaps(events, w0, w1):
            idle.append((innermost(spans, (s + e) / 2, "outside every span"),
                         (e - s) / 1e9))
    return {"device_ops": top(ops.items()), "idle_gaps": top(idle)}
