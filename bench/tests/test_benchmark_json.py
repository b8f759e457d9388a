"""BENCHMARK.json keeps to its contract, and every name in it leads to
its files."""
import json
import os
import re

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def bm():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_top_level_keys():
    b = bm()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"]
    assert 1 <= b["run_seconds"] <= 51


def test_entries_and_files():
    b = bm()
    configs = {c["name"] for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and os.path.isfile(
            os.path.join(harness.ROOT, c["file"]))
    used = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        wl = harness.read_json(harness.named_file("workloads", w["name"],
                                                  ".json"))
        assert wl["config"] == w["config"]
        harness.named_file("drivers", wl["driver"], ".py")
        used.add(w["config"])
    assert used == configs
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25
               for m in b["end_to_end"])
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        harness.named_file("metrics", m["name"], ".py")


def test_every_cell_reports_enough():
    b = bm()
    for w in b["workloads"]:
        e2e = {m["name"] for m in harness.metrics_of(b, w["name"], False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        per_layer = harness.metrics_of(b, w["name"], True)
        assert per_layer and all(m["moves"] in e2e for m in per_layer)
