"""Readings that set the limits of `correct`, in one process.

    python3 bench/control.py --workload <cell> --seeds 11,12,... --seconds 10

For each seed it makes a run of the cell as `run.py` does (set-up, window,
drain) and reads the numbers compared twice on the same answered batches:

* the program's — what the timed path produced against the float64
  reference (the lower readings: the largest over a dozen seeds);
* the control's — the same reference computed in bfloat16, the nearest
  precision below the configuration's float32, put in the program's
  place (the upper readings: the smallest over the seeds).

A limit lies between the two with room on both sides. Needs the cell's
TPU, like `run.py`.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

from yardstick import checks, harness          # noqa: E402


def readings(record) -> dict:
    """Program and control gaps on the batches a run answered."""
    dep = record["_deployment"]
    found = checks.compare(dep.reference(), record["_answers"],
                           control=dep.reference("bfloat16"),
                           whole_graph=record.get("_whole_graph", False))
    return {k: found[k] for k in ("compared", "exit_gap", "logit_gap",
                                  "control_exit_gap", "control_logit_gap")}


def main(argv=None) -> int:
    import run as bench_run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload, False)
    devices = bench_run.require_chips(cell.chips)
    if devices is None:
        return bench_run.EXIT_NO_CHIP
    import jax
    jax.config.update("jax_compilation_cache_dir", harness.compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = harness.Context(cell=cell, seed=seed, seconds=args.seconds,
                              trace=False, t_start=time.perf_counter(),
                              out_dir=ROOT / "bench" / ".out" / cell.name,
                              device_kind=devices[0].device_kind,
                              keep_answers=True)
        ctx.out_dir.mkdir(parents=True, exist_ok=True)
        rec = cell.driver.run(ctx)
        row = {"seed": seed, **readings(rec)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    keys = ("exit_gap", "logit_gap")
    print(json.dumps({
        "lower": {k: max(r[k] for r in rows) for k in keys},
        "upper": {k: min(r[f"control_{k}"] for r in rows) for k in keys},
        "seeds": len(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
