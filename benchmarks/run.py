"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (us_per_call = 0.0 for analytic /
counting benchmarks where wall time is not the measurand).  JSON artifacts
land in results/bench/; the engine's perf trajectory (serial -> numpy
engine -> jitted jax backend) is additionally written to
``BENCH_engine.json`` at the repo root so speedups are trackable across
PRs without digging through per-run artifacts.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import traceback

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench(name: str):
    """Load a results/bench artifact, preferring the cwd-relative copy
    (_dump() writes cwd-relative; freshest when run from the repo root)
    with the repo-root copy as a fallback for out-of-tree invocations."""
    candidates = [
        os.path.join("results", "bench", f"{name}.json"),
        os.path.join(_REPO_ROOT, "results", "bench", f"{name}.json"),
    ]
    src = next((p for p in candidates if os.path.exists(p)), None)
    if src is None:
        return None
    with open(src) as fh:
        return json.load(fh)


def _provenance() -> dict:
    """Stamp for refreshed sections: which software/hardware produced the
    timings (jax/jaxlib versions, device kind and count, platform, git
    commit) — so a BENCH_engine.json diff is interpretable months later
    without spelunking CI logs.  A failure to read the devices raises:
    a timing without its device is not recorded."""
    import platform
    import subprocess

    import jax
    import jaxlib

    devs = jax.devices()
    info: dict = {"python": platform.python_version(),
                  "platform": platform.platform(),
                  "jax": jax.__version__, "jaxlib": jaxlib.__version__,
                  "backend": devs[0].platform,
                  "device_kind": devs[0].device_kind,
                  "device_count": len(devs)}
    try:                      # a copy of the tree need not be a git checkout
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=_REPO_ROOT,
            capture_output=True, text=True, timeout=10)
        info["git_commit"] = out.stdout.strip() or None
    except Exception:                                     # noqa: BLE001
        info["git_commit"] = None
    return info


# warm-timing regression gate: a refreshed row whose config matches the
# committed BENCH_engine.json row must not be more than 10% slower.
# Override with REPRO_BENCH_ALLOW_REGRESSION=1 (recorded in the summary,
# so a waved-through regression is still visible in the diff).
_REGRESSION_TOLERANCE = 1.10


def _guard_regressions(prev: dict, summary: dict) -> None:
    """Compare warm timings of matching-config rows old vs new.

    Only rows whose full config tuple matches are compared (CI's
    reduced-scale env knobs produce different configs and sail
    through); carried-over sections compare equal and report ratio 1.
    Ratios land in ``summary["regression_guard"]``; a ratio above the
    tolerance raises unless REPRO_BENCH_ALLOW_REGRESSION is set.
    """
    checks = []   # (label, old_s, new_s)

    def _rows(d: dict, section: str, key: tuple):
        """index a section's timing rows by their full config tuple;
        fused sweep rows inherit (trials, steps) from the section."""
        sec = d.get(section)
        if sec is None:
            return {}
        if section == "numpy_vs_jax":                  # bare row list
            rows = sec
        elif section in ("fused", "gram"):             # sweep-row sections
            rows = [{**r, "trials": sec.get("trials"),
                     "steps": sec.get("steps")} for r in sec.get("sweep", [])]
        else:                                          # single-row dict
            rows = [sec]
        return {tuple(r.get(k) for k in key): r for r in rows}

    plans = [
        ("numpy_vs_jax", ("d", "trials", "steps"), ["jax_warm_s"]),
        ("adaptive", ("trials", "steps", "d"), ["device_warm_s"]),
        ("schedule_build", ("trials", "steps"), ["vector_s"]),
        ("fused", ("d", "trials", "steps"), ["fused_s", "unfused_s"]),
        ("gram", ("d", "trials", "steps"), ["gram_s", "fused_s"]),
        ("telemetry_overhead", ("d", "trials", "steps"),
         ["off_s", "on_s"]),
    ]
    for section, key, fields in plans:
        old_rows = _rows(prev, section, key)
        new_rows = _rows(summary, section, key)
        for cfg, new_r in new_rows.items():
            old_r = old_rows.get(cfg)
            if old_r is None:
                continue
            for f in fields:
                if f in old_r and f in new_r and old_r[f] > 0:
                    checks.append((f"{section}[{cfg}].{f}",
                                   old_r[f], new_r[f]))

    ratios = {label: new_s / old_s for label, old_s, new_s in checks}
    regressed = {label: round(r, 3) for label, r in ratios.items()
                 if r > _REGRESSION_TOLERANCE}
    allowed = bool(os.environ.get("REPRO_BENCH_ALLOW_REGRESSION"))
    summary["regression_guard"] = {
        "tolerance": _REGRESSION_TOLERANCE,
        "compared": len(checks),
        "ratios": {label: round(r, 3) for label, r in ratios.items()},
        "regressed": regressed,
        "allowed_by_env": allowed and bool(regressed),
    }
    if regressed and not allowed:
        raise RuntimeError(
            f"warm-timing regression(s) beyond "
            f"{(_REGRESSION_TOLERANCE - 1) * 100:.0f}% vs the committed "
            f"BENCH_engine.json: {regressed} — set "
            f"REPRO_BENCH_ALLOW_REGRESSION=1 to accept deliberately")


def write_bench_engine() -> None:
    """Summarize the engine benchmarks into BENCH_engine.json (repo root).

    Tracked fields: the serial->engine speedup (engine_speedup), the
    numpy-engine->jax-backend d sweep (backend_sweep) with parity bits,
    the fused and gram data-plane sweeps (megakernel vs unfused oracle;
    coefficient-space scan vs megakernel), the control-plane
    schedule-build column (vectorized replay vs the full-engine proxy
    replay), and the multi-device scaling smoke (unsharded vs
    8-device-sharded trial batches, speedup expected only on real
    accelerator meshes).  Refreshed rows are gated by
    :func:`_guard_regressions` against the committed file.
    """
    # start from the committed summary so a partial run (e.g. the CI
    # adaptive-smoke job, which produces only the adaptive artifact)
    # refreshes its own rows without dropping the others
    bench_path = os.path.join(_REPO_ROOT, "BENCH_engine.json")
    summary = {}
    if os.path.exists(bench_path):
        with open(bench_path) as fh:
            summary = json.load(fh)
    prev = json.loads(json.dumps(summary))   # deep copy of the baseline
    # retired field: the 3x-at-1M target graduated into the per-row
    # regression guard (and the gram plane moved the goalposts anyway)
    summary.pop("jax_target_3x_at_1M", None)
    # provenance is computed once per run and stamped per *refreshed*
    # section, so carried-over rows keep the stamp of the run that
    # actually produced them
    prov = _provenance()

    def _stamp(*sections: str) -> None:
        for s in sections:
            summary.setdefault("meta", {})[s] = prov

    data = _load_bench("engine_speedup")
    if data is not None:
        sweep = data.get("backend_sweep", [])
        summary["serial_vs_engine"] = {
            "trials": data.get("trials"),
            "steps": data.get("steps"),
            "speedup": data.get("speedup"),
            "bitwise_mismatches": data.get("bitwise_mismatches"),
        }
        summary["numpy_vs_jax"] = [
            {k: row[k] for k in ("d", "trials", "steps", "numpy_s",
                                 "jax_warm_s", "jax_cold_s", "speedup",
                                 "control_parity", "value_parity")}
            for row in sweep
        ]
        _stamp("serial_vs_engine", "numpy_vs_jax")
    adaptive = _load_bench("adaptive_sweep")
    if adaptive is not None:
        summary["adaptive"] = {
            **adaptive,
            "target_5x_met": adaptive.get("speedup", 0.0) >= 5.0,
        }
        _stamp("adaptive")
    sched = _load_bench("schedule_build")
    if sched is not None:
        summary["schedule_build"] = {
            **sched,
            "target_3x_met": sched.get("speedup", 0.0) >= 3.0,
        }
        _stamp("schedule_build")
    devices = _load_bench("engine_devices")
    if devices is not None:
        summary["devices_scaling"] = devices
        _stamp("devices_scaling")
    fused = _load_bench("fused_sweep")
    if fused is not None:
        rows = fused.get("sweep", [])
        summary["fused"] = {
            "trials": fused.get("trials"),
            "steps": fused.get("steps"),
            "target": fused.get("target"),
            "sweep": rows,
            "target_met": all(r["target_met"] for r in rows) if rows
            else None,
        }
        _stamp("fused")
    gram = _load_bench("gram_sweep")
    if gram is not None:
        rows = gram.get("sweep", [])
        summary["gram"] = {
            "trials": gram.get("trials"),
            "steps": gram.get("steps"),
            "target": gram.get("target"),
            "sweep": rows,
            "target_met": all(r["target_met"] for r in rows) if rows
            else None,
        }
        _stamp("gram")
    tel = _load_bench("telemetry_overhead")
    if tel is not None:
        summary["telemetry_overhead"] = tel
        _stamp("telemetry_overhead")
    _guard_regressions(prev, summary)
    # atomic replace: an interrupted run (ctrl-C mid-dump, OOM-killed CI
    # job) must never truncate the merged results file
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(bench_path),
                               prefix=".BENCH_engine.", suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as fh:
            json.dump(summary, fh, indent=1)
            fh.write("\n")
        os.replace(tmp, bench_path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


def _suites():
    from benchmarks import bench_kernels, bench_protocol, bench_train

    return bench_protocol.ALL + bench_kernels.ALL + bench_train.ALL


def main(argv=None) -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    suites = _suites()
    by_name = {fn.__name__: fn for fn in suites}
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--only", metavar="SECTION", default=None,
        help="run a single bench section by function name; one of: "
        + ", ".join(sorted(by_name)))
    args = ap.parse_args(argv)
    if args.only is not None:
        if args.only not in by_name:
            ap.error(f"unknown section {args.only!r}; available: "
                     + ", ".join(sorted(by_name)))
        suites = [by_name[args.only]]
    print("name,us_per_call,derived")
    from repro.obs import trace as obtrace

    failures = 0
    for fn in suites:
        try:
            # span per suite fn (profile_trace itself is used inside the
            # suites around the warm timed runs — nesting a second
            # jax.profiler.trace here would fail, so the outer layer is
            # span-only)
            with obtrace.span(f"bench.{fn.__name__}"):
                for name, us, derived in fn():
                    print(f"{name},{us:.1f},{derived}", flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{fn.__name__},0.0,ERROR:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    write_bench_engine()
    trace_out = os.environ.get("REPRO_TRACE_OUT")
    if trace_out:
        obtrace.export_chrome(trace_out)
        print(f"chrome trace: {trace_out}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
