"""The per-layer metrics read from the program's own records
(`repro.obs`): after a tiny run of each driver with the trace off, every
one is present and finite, read from the window's records and from no
others, and left out where the selection does not match the run."""
import math
import time

import pytest

import tinybench

SERVE = ["queue_wait_ms.serve", "sample_ms.serve", "gather_ms.serve",
         "pack_ms.serve", "dispatch_ms.serve", "hold_ms.serve",
         "sync_ms.serve", "useful_rows.serve", "useful_edges.serve"]
OFFLINE = ["upload_s.offline", "fetch_s.offline", "classify_s.offline",
           "result_s.offline"]
CELL = {**{m: "tiny.serve" for m in SERVE},
        **{m: "tiny.offline" for m in OFFLINE}}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """Each tiny cell run once through its driver, trace off: its cell
    (with the per-layer metrics), record and metric values."""
    from yardstick import harness
    root = tinybench.make_root(tmp_path_factory.mktemp("spans"))
    out = {}
    for name, seconds in (("tiny.serve", 1.0), ("tiny.offline", 0.5)):
        cell = harness.find_cell(name, True, root)
        ctx = harness.Context(cell=cell, seed=2**33 + 9, seconds=seconds,
                              trace=False, t_start=time.perf_counter(),
                              out_dir=root / "bench" / ".out" / name,
                              log=lambda msg: None)
        ctx.out_dir.mkdir(parents=True, exist_ok=True)
        rec = cell.driver.run(ctx)
        out[name] = (rec, harness.read_metrics(cell, rec, root / "bench"))
    return root, out


def _reader(root, name):
    from yardstick import harness
    return harness._module(root / "bench" / "metrics" / f"{name}.py", name)


@pytest.mark.parametrize("name", SERVE + OFFLINE)
def test_new_metric_is_read_and_finite(runs, name):
    _, out = runs
    metrics = out[CELL[name]][1]
    assert name in metrics, sorted(metrics)
    v = metrics[name]["value"]
    assert math.isfinite(v) and v >= 0.0
    if metrics[name]["unit"] == "fraction":
        assert 0.0 < v <= 1.0


def test_serving_records_are_the_windows_batches(runs):
    from yardstick import spans
    rec = runs[1]["tiny.serve"][0]
    sel = spans.serve_batches(rec)
    assert [r["host_s"] for r in sel] == rec["host_s"]
    assert sum(r["n"] for r in sel) == rec["served"]
    assert [r["batch"] for r in sel] == sorted(r["batch"] for r in sel)
    # the engine's own host stage metric reads the same batches
    host = _reader(runs[0], "host_stage_ms.serve").read(rec)
    assert host == pytest.approx(1e3 * spans.mean(sel, "host_s"))


def test_offline_records_are_the_windows_jobs(runs):
    from yardstick import spans
    rec = runs[1]["tiny.offline"][0]
    sel = spans.offline_jobs(rec)
    assert [r["pack_s"] for r in sel] == rec["pack_s"]
    assert [r["ckpt_s"] for r in sel] == rec["ckpt_s"]
    for r in sel:
        assert r["nodes_per_s"] == rec["nodes"] / r["total_s"]


@pytest.mark.parametrize("name", SERVE + OFFLINE)
def test_a_record_that_does_not_match_the_log_reads_nothing(runs, name):
    root, out = runs
    rec = dict(out[CELL[name]][0])
    count = "batches" if name in SERVE else "jobs"
    reader = _reader(root, name)
    assert reader.read(rec) is not None
    rec[count] += 1
    assert reader.read(rec) is None
    rec[count] = 0
    assert reader.read(rec) is None
    other = out["tiny.offline" if name in SERVE else "tiny.serve"][0]
    assert reader.read(other) is None
