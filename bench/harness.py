"""One run of one cell: set-up, the measured window, the check, and the
result line.

Everything that belongs to one cell is found by name:

- ``BENCHMARK.json`` at the checkout's root lists the cell, its chips and
  the metrics it reports;
- ``bench/workloads/<cell>.json`` holds the cell's configuration name,
  its driver kind, the traffic parameters and the limits of its check;
- ``bench/configs/<config>.json`` holds the configuration;
- ``bench/drivers/<kind>.py`` drives the program under that traffic;
- ``bench/metrics/<metric>.py`` reads one metric (``read(ctx)``), or
  returns None where the run has nothing for it to read.

A run sets up and warms up every shape the window will use (that is
``setup_s``), then lets one client call the program in a closed loop for
``--seconds`` seconds: each call starts when the last one returned, and
the last call that starts inside the window runs to its end.  With
``--trace 1`` the window runs under the JAX profiler, and the per-layer
metrics are read from the trace and from the program's spans.  After the
window the program's state is released and the driver compares what the
window produced with the plain reference.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import re
import shutil
import statistics
import sys
import time
import traceback
import types

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class Refused(RuntimeError):
    """The run cannot measure what it was asked to; no result is printed."""


def read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def named_file(kind: str, name: str, suffix: str) -> str:
    if not NAME.match(name):
        raise Refused(f"not a valid {kind} name: {name!r}")
    path = os.path.join(BENCH, kind, name + suffix)
    if not os.path.isfile(path):
        raise Refused(f"no {kind} file for {name!r} ({path})")
    return path


def load_module(kind: str, name: str):
    path = named_file(kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_entry(bm: dict, cell: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == cell:
            return w
    raise Refused(f"BENCHMARK.json has no workload {cell!r}")


def metrics_of(bm: dict, cell: str, trace: bool) -> list[dict]:
    """The end-to-end metrics the cell reports, or with ``trace`` its
    per-layer metrics: those that list the cell, and those without a
    list whose end-to-end metric the cell reports."""
    e2e = [m for m in bm["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    moves = {m["name"] for m in e2e}
    return [m for m in bm["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moves)]


def accelerators(chips: int):
    """The devices a cell runs on, their kind and its peaks."""
    import jax

    from bench.peaks import UnknownDevice, peaks_for

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu":
        raise Refused(f"needs a TPU; JAX found {platform!r}")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips; JAX found "
                      f"{len(devices)}")
    try:
        peaks = peaks_for(kind)
    except UnknownDevice as e:
        raise Refused(str(e)) from None
    return devices[:chips], platform, kind, peaks


@contextlib.contextmanager
def compile_counter():
    """Counts JAX's trace, lower and compile events while ``armed``."""
    import jax

    state = {"armed": False, "n": 0}

    def listen(event, duration, **_):
        if state["armed"] and event in COMPILE_EVENTS:
            state["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield state
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def window(driver, seconds: float, counter: dict, annotate) -> list[dict]:
    """The closed loop: call after call until ``seconds`` have passed
    since the first started.  Returns one record per call."""
    records = []
    counter["armed"] = True
    t_first = time.perf_counter_ns()
    i = 1
    while True:
        t0 = time.perf_counter_ns()
        if t0 - t_first >= seconds * 1e9:
            break
        err = None
        with annotate(driver.span_name):
            try:
                work, out = driver.call(i)
            except Exception:                       # counted as failed
                err, work, out = traceback.format_exc(), 0, None
        t1 = time.perf_counter_ns()
        records.append({"i": i, "t0": t0, "t1": t1, "work": work,
                        "error": err})
        if out is not None:
            driver.keep(i, out)
        i += 1
    counter["armed"] = False
    return records


def _trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def reduce_trace(trace_dir: str, devices, records, program_spans,
                 span_name: str) -> dict:
    """The trace on the host's clock: device events of the cell's chips,
    the window [w0, w1], the offset from the host's perf counter to the
    trace's clock, and the host spans (harness and program) moved onto
    the trace's clock."""
    from bench import tracereduce as tr

    raw = tr.load(trace_dir, {span_name})
    print("trace device planes " + json.dumps(
        {k: len(v) for k, v in raw["devices"].items()}), file=sys.stderr)
    ann = [e for e in raw["host"] if e[2] == span_name]
    if len(ann) != len(records):
        raise Refused(f"the trace holds {len(ann)} {span_name} spans for "
                      f"{len(records)} calls")
    offset = statistics.median(a[0] - r["t0"] for a, r in zip(ann, records))
    ids = {f"{d.platform.upper()}:{d.id}" for d in devices}
    devs = {k: v for k, v in raw["devices"].items() if k in ids}
    if not devs:
        raise Refused(f"no device plane for {sorted(ids)} in the trace "
                      f"(planes: {sorted(raw['devices'])})")
    spans = ann + [[s["ts_ns"] + offset, s["dur_ns"], s["name"], ""]
                   for s in program_spans]
    return {"devices": devs, "w0": records[0]["t0"] + offset,
            "w1": records[-1]["t1"] + offset, "spans": spans}


def run(cell: str, seed: int, seconds: float, trace: bool, *,
        t_start: float) -> dict:
    """One run of ``cell``; returns the result line as a dict.
    ``t_start`` is the process's start on ``time.perf_counter``."""
    bm = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = cell_entry(bm, cell)
    workload = read_json(named_file("workloads", cell, ".json"))
    if workload["config"] != entry["config"]:
        raise Refused(f"{cell}: workload file names config "
                      f"{workload['config']!r}, BENCHMARK.json "
                      f"{entry['config']!r}")
    config = read_json(named_file("configs", entry["config"], ".json"))
    devices, platform, kind, peaks = accelerators(entry["chips"])

    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.obs import trace as obtrace

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    driver_mod = load_module("drivers", workload["driver"])
    driver = driver_mod.Driver(config, workload["traffic"], seed, devices)

    with compile_counter() as counter:
        driver.warm_up()
        setup_s = time.perf_counter() - t_start
        obtrace.clear()
        trace_dir = os.path.join(ROOT, ".bench_trace", cell)
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            with jax.profiler.trace(trace_dir,
                                    profiler_options=_trace_options()):
                records = window(driver, seconds, counter,
                                 jax.profiler.TraceAnnotation)
        else:
            records = window(driver, seconds, counter,
                             lambda name: contextlib.nullcontext())
    program_spans = obtrace.spans()
    memory = peak_bytes(devices)
    print(f"compiles_in_window {counter['n']}", file=sys.stderr, flush=True)

    ctx = types.SimpleNamespace(
        config=config, traffic=workload["traffic"], peaks=peaks,
        setup_s=setup_s, records=records,
        window_s=(records[-1]["t1"] - records[0]["t0"]) / 1e9,
        program_spans=[s for s in program_spans
                       if s["ts_ns"] >= records[0]["t0"]
                       and s["ts_ns"] + s["dur_ns"] <= records[-1]["t1"]],
        trace=None)
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory}
    breakdown = None
    if trace:
        from bench import tracereduce as tr

        ctx.trace = reduce_trace(trace_dir, devices, records,
                                 ctx.program_spans, driver.span_name)
        t = ctx.trace
        busy = [tr.busy_ns(ev, t["w0"], t["w1"]) / 1e9
                for ev in t["devices"].values()]
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = (t["w1"] - t["w0"]) / 1e9
        breakdown = tr.breakdown(t["devices"], t["spans"], t["w0"], t["w1"])
        shutil.rmtree(trace_dir, ignore_errors=True)

    driver.release()
    failed = [r for r in records if r["error"]]
    for r in failed[:1]:
        print(r["error"], file=sys.stderr, flush=True)
    t_ref = time.perf_counter()
    checks = driver.verify(workload["limits"])
    print(f"reference_s {time.perf_counter() - t_ref}", file=sys.stderr,
          flush=True)
    metrics = {}
    for m in metrics_of(bm, cell, trace):
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    correct = not failed and all(c["ok"] for c in checks)
    result = {"correct": correct,
              "attempted": len(records) * driver.items_per_call,
              "failed": len(failed) * driver.items_per_call,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell and print its result line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), t_start=t_start)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
