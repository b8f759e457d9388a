"""Check that the program's spans reach the profiler's trace.

    python3 bench/spanaudit.py --workload <cell> --seed <n> --seconds <s>

Makes one traced run of the cell, as ``bench/run.py --trace 1`` does, and
prints its result line the same way.  Before the trace is thrown away it
also reads the program's spans from the trace itself (every span of
``repro.obs.trace`` is a profiler annotation of the same name) and
prints to standard error:

- ``program_spans``: per span name, the count in the trace against the
  count in the span ring buffer over the window;
- ``span_clock_skew_us``: the largest distance between a span's start
  moved onto the trace's clock by the harness's one offset and its start
  as the profiler recorded it;
- ``spans_dropped``: spans the full ring buffer evicted in the window;
- ``untraced_idle_pct_recorded``: the share of the window in which the
  chip is idle and no recorded span covers the host;
- ``outside_spans_pct``: where the host is under no program span, as
  shares of the window: ``head`` from a call's start to its first span
  (the driver's trial specs, the program's checks of them), ``tail``
  from its last span to its end (the return, and the release of the
  previous call's result), ``between`` calls (the harness's records);
- ``trial_steps_per_s_traced``: the end-to-end rate of this traced run.
"""
import json
import os
import sys
import time
import types

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def audit(moved, recorded) -> dict:
    """Per-name counts {name: [in trace, in ring buffer]} and the largest
    start skew in microseconds between the ring buffer's spans moved
    onto the trace's clock (``moved``) and the trace's own (``recorded``),
    both [start, dur, name, ...], paired in order within each name that
    has as many of each."""
    by_name: dict[str, list[list]] = {}
    for which, spans in enumerate((recorded, moved)):
        for s in spans:
            by_name.setdefault(s[2], [[], []])[which].append(s[0])
    skew = 0.0
    for rec, mov in by_name.values():
        if len(rec) == len(mov):
            skew = max([skew] + [abs(a - b) / 1e3 for a, b
                                 in zip(sorted(rec), sorted(mov))])
    counts = {k: [len(v[0]), len(v[1])] for k, v in sorted(by_name.items())}
    return {"counts": counts, "skew_us": skew}


def outside_spans(calls, spans, w0, w1) -> dict:
    """Percent of [w0, w1] from each call's start to its first span
    (``head``), from its last span's end to its end (``tail``), and from
    one call's end to the next's start (``between``); ``calls`` and
    ``spans`` as [start, dur, name, ...]."""
    out = {"head": 0.0, "tail": 0.0, "between": 0.0}
    calls = sorted(calls)
    for k, (c0, cd, *_) in enumerate(calls):
        inner = [s for s in spans if c0 <= s[0] and s[0] + s[1] <= c0 + cd]
        if inner:
            out["head"] += min(s[0] for s in inner) - c0
            out["tail"] += c0 + cd - max(s[0] + s[1] for s in inner)
        if k + 1 < len(calls):
            out["between"] += calls[k + 1][0] - (c0 + cd)
    return {k: 100.0 * v / (w1 - w0) for k, v in out.items()}


def audited(reduce_trace):
    """``harness.reduce_trace`` that also compares the recorded spans."""
    from bench import harness, tracereduce as tr
    from repro.obs import trace as obtrace

    untraced = harness.load_module("metrics", "untraced_idle_pct.sweep")
    rate = harness.load_module("metrics", "trial_steps_per_s")

    def wrapper(trace_dir, devices, records, program_spans, span_name):
        t = reduce_trace(trace_dir, devices, records, program_spans,
                         span_name)
        names = {s["name"] for s in program_spans}
        recorded = tr.load(trace_dir, names)["host"]
        moved = [s for s in t["spans"] if s[2] != span_name]
        calls = [s for s in t["spans"] if s[2] == span_name]
        a = audit(moved, recorded)
        idle = [untraced.untraced_ns(ev, recorded, t["w0"], t["w1"])
                for ev in t["devices"].values()]
        edges = outside_spans(calls, recorded, t["w0"], t["w1"])
        tsps = rate.read(types.SimpleNamespace(records=records))
        for line in (f"program_spans {json.dumps(a['counts'])}",
                     f"span_clock_skew_us {a['skew_us']}",
                     f"spans_dropped {obtrace.dropped()}",
                     "untraced_idle_pct_recorded "
                     f"{100.0 * sum(idle) / len(idle) / (t['w1'] - t['w0'])}",
                     f"outside_spans_pct {json.dumps(edges)}",
                     f"trial_steps_per_s_traced {tsps}"):
            print(line, file=sys.stderr, flush=True)
        return t

    return wrapper


def main(argv=None) -> int:
    from bench import harness

    args = list(sys.argv[1:] if argv is None else argv)
    harness.reduce_trace = audited(harness.reduce_trace)
    return harness.main(args + ["--trace", "1"], t_start=T_START)


if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # the TPU runtime logs to a fixed directory under /tmp unless told
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
