"""A configuration, a traffic mix and a metric added as new files plus new
`BENCHMARK.json` entries are found and run with no other file changed;
and the committed `BENCHMARK.json` keeps to the benchmark's contract."""
import hashlib
import json
import re
import time
from pathlib import Path

import pytest

import tinybench
from test_bench_drivers import run_cell
from yardstick import harness

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")

NEW_METRIC = '''"""Requests served in the window (a test metric)."""


def read(rec):
    return rec.get("served")
'''


def _digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts and ".out" not in p.parts
            and ".jax_cache" not in p.parts}


def test_new_files_are_found_by_name(tmp_path, capsys):
    root = tinybench.make_root(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    before = _digest(root / "bench")
    cfg = dict(tinybench.TINY_CONFIG, name="tiny2-sgc",
               graph=dict(tinybench.TINY_CONFIG["graph"], seed=9, nodes=500))
    (root / "bench/configs/tiny2-sgc.json").write_text(json.dumps(cfg))
    mix = dict(tinybench.TINY_SERVE, rate_rps=30, gap_seed=4)
    (root / "bench/traffic/slow-serve.json").write_text(json.dumps(mix))
    (root / "bench/metrics/served.serve.py").write_text(NEW_METRIC)
    spec["configs"].append({"name": "tiny2-sgc", "source": "https://x.org",
                            "file": "bench/configs/tiny2-sgc.json",
                            "reduced": ["nodes"], "why": "tests"})
    spec["workloads"].append({"name": "tiny2.slow", "config": "tiny2-sgc",
                              "traffic": "slow-serve", "chips": 1,
                              "why": "tests"})
    for m in spec["end_to_end"]:
        if "tiny.serve" in m.get("workloads", []):
            m["workloads"].append("tiny2.slow")
    spec["per_layer"].append({"name": "served.serve", "unit": "requests",
                              "better": "higher", "source": "program_counter",
                              "layer": "front-end and batch former",
                              "moves": "latency_p95_ms",
                              "workloads": ["tiny2.slow"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    after = _digest(root / "bench")
    assert all(after[k] == v for k, v in before.items())

    cell = harness.find_cell("tiny2.slow", True, root)
    assert cell.config["graph"]["nodes"] == 500
    assert cell.traffic["rate_rps"] == 30
    assert [m["name"] for m in cell.metrics] == ["served.serve"]
    got = harness.read_metrics(cell, {"served": 7}, root / "bench")
    assert got == {"served.serve": {"value": 7.0, "unit": "requests"}}
    assert harness.read_metrics(cell, {}, root / "bench") == {}

    out = run_cell(root, "tiny2.slow", capsys)
    assert out["correct"] is True
    assert out["attempted"] == 30
    assert "latency_p95_ms" in out["metrics"]


def test_unknown_cell_is_an_error(tmp_path):
    root = tinybench.make_root(tmp_path)
    with pytest.raises(KeyError):
        harness.find_cell("no.such-cell", False, root)


# ------------------------------------------------------------- contract
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][:2] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = SPEC["run_seconds"]
    assert 1 <= rs <= 51 and isinstance(rs, int)
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_entries():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = ([c["name"] for c in SPEC["configs"]]
             + [w["name"] for w in SPEC["workloads"]]
             + [m["name"] for m in metrics])
    assert all(NAME.match(n) for n in names)
    for group in (SPEC["configs"], SPEC["workloads"], metrics):
        assert len({x["name"] for x in group}) == len(group)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").is_file()
        if m["name"].endswith("_roofline") or "roofline" in m["name"] \
                or "mfu" in m["name"]:
            assert m["unit"] == "%"
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_every_cell_reports_setup_another_metric_and_a_layer():
    pairs = set()
    for w in SPEC["workloads"]:
        assert w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        traffic = json.loads(
            (ROOT / "bench/traffic" / f"{w['traffic']}.json").read_text())
        assert (ROOT / "bench/drivers" / f"{traffic['driver']}.py").is_file()

        def has(group):
            return [m["name"] for m in SPEC[group]
                    if w["name"] in m.get("workloads", [w["name"]])]
        e2e = has("end_to_end")
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = has("per_layer")
        assert layer
        for m in SPEC["per_layer"]:
            if m["name"] in layer:
                assert m["moves"] in e2e
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(SPEC["workloads"]) // 2)
