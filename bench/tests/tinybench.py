"""A tiny benchmark checkout for tests on the CPU: the real drivers and
metric readers over a small graph, with the chip check stubbed."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

TINY_CONFIG = {
    "name": "tiny-sgc",
    "source": "https://arxiv.org/abs/2005.00687",
    "graph": {"nodes": 600, "edges": 2400, "features": 16, "classes": 4,
              "seed": 3},
    "model": {"base_model": "sgc", "k": 3, "r": 0.5, "t_min": 1, "t_max": 3},
    "engine": {"mode": "compiled", "spmm_impl": "segment",
               "pipeline_depth": 2, "batch_size": 16, "cache_nodes": 0},
    "precision": "float32",
    "correct": {"exit_gap": 1e-4, "logit_gap": 1e-3},
    "reduced": ["nodes", "edges", "features", "classes"],
}
TINY_SERVE = {"driver": "open_loop", "rate_rps": 60, "gap_seed": 0,
              "slo_class": {"name": "gold", "deadline_s": 5.0,
                            "max_wait_s": 0.05, "queue_depth": 64}}
TINY_OFFLINE = {"driver": "offline_jobs"}


def make_root(tmp: Path) -> Path:
    """A checkout with BENCHMARK.json naming tiny cells, the real drivers,
    metric readers and library, and tiny configuration and traffic files."""
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench = tmp / "bench"
    for d in ("drivers", "metrics", "yardstick"):
        shutil.copytree(BENCH / d, bench / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "configs").mkdir()
    (bench / "traffic").mkdir()
    (bench / "configs" / "tiny-sgc.json").write_text(json.dumps(TINY_CONFIG))
    (bench / "traffic" / "tiny-serve.json").write_text(json.dumps(TINY_SERVE))
    (bench / "traffic" / "tiny-offline.json").write_text(
        json.dumps(TINY_OFFLINE))
    spec["configs"] = [{"name": "tiny-sgc", "source": TINY_CONFIG["source"],
                        "file": "bench/configs/tiny-sgc.json",
                        "reduced": TINY_CONFIG["reduced"], "why": "tests"}]
    cells = {"tiny.serve": "tiny-serve", "tiny.offline": "tiny-offline"}
    spec["workloads"] = [{"name": n, "config": "tiny-sgc", "traffic": t,
                          "chips": 1, "why": "tests"}
                         for n, t in cells.items()]
    rename = {"arxiv-sgc.serve-uniform": "tiny.serve",
              "flickr-sgc.offline": "tiny.offline"}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename.get(w, w) for w in m["workloads"]]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def add_sharded_offline(root: Path, chips: int) -> str:
    """A cell of whole-graph jobs on `chips` devices of the tiny graph,
    whose configuration exchanges rows by all-to-all; its name."""
    cfg = dict(TINY_CONFIG, name="tiny-sgc-a2a",
               engine=dict(TINY_CONFIG["engine"], gather_mode="alltoall"))
    (root / "bench" / "configs" / "tiny-sgc-a2a.json").write_text(
        json.dumps(cfg))
    name = f"tiny.offline-d{chips}"
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-sgc-a2a", "source": cfg["source"],
                            "file": "bench/configs/tiny-sgc-a2a.json",
                            "reduced": cfg["reduced"], "why": "tests"})
    spec["workloads"].append({"name": name, "config": "tiny-sgc-a2a",
                              "traffic": "tiny-offline", "chips": chips,
                              "why": "tests"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny.offline" in m.get("workloads", []):
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return name


def any_device(chips):
    import jax
    return jax.devices()
