"""Offer a serving cell at a ladder of fixed rates, in one process, and
print for each rate how many requests met the deadline, the backlog the
window left behind and how late the arrival generator ran.

    python3 bench/sweep.py --workload arxiv-sgc.serve-uniform \\
        --rates 100,200,400,800 --seconds 8 [--seed 1]

The knee is the highest rate at which at least 90% of the offered
requests meet the deadline with no growing backlog; a cell that measures
tails offers about four fifths of it, written into its traffic file as a
number. Needs the TPU the cell asks for, like `run.py`.
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

from yardstick import arrivals, harness            # noqa: E402
from yardstick.measure import percentile           # noqa: E402


def sweep_rate(drv, fe, dep, traffic, rate, seconds, seed, deadlines=()):
    """One open-loop window at `rate`; the row of the sweep's table."""
    slo = traffic["slo_class"]["name"]
    times = arrivals.poisson_schedule(rate, seconds, seed=seed,
                                      gap_seed=traffic["gap_seed"])
    nodes = arrivals.uniform_nodes(dep.graph.test_idx, len(times), seed=seed)
    fe.reset_stats()
    reqs, submitted, start, close = drv.serve_window(fe, times, nodes, slo)
    backlog = fe.pending()
    half = [r for r, t in zip(reqs, times) if t >= seconds / 2]
    drv.drain(fe)
    lat = [(r.done_s - r.arrival_s) if r is not None and r.status == "completed"
           else float("inf") for r in reqs]
    hits = sum(1 for r in reqs if r is not None and r.within_deadline)
    late50, late_max = arrivals.lateness(times, submitted)
    eng = next(iter(fe.engines.values()))
    return {
        "rate_rps": rate, "offered": len(times), "met": hits,
        "attainment": hits / len(times),
        "attainment_2nd_half": (sum(1 for r in half if r is not None
                                    and r.within_deadline) / max(len(half), 1)),
        "backlog_at_close": backlog,
        "shed": sum(r is None for r in reqs),
        "p50_ms": 1e3 * percentile(lat, 50)[0],
        "p95_ms": 1e3 * percentile(lat, 95)[0],
        "mean_batch": eng.stats.served / max(eng.stats.batches, 1),
        "late_p50_ms": 1e3 * late50, "late_max_ms": 1e3 * late_max,
        **{f"met_{d:g}s": sum(x <= d for x in lat) / len(lat)
           for d in deadlines},
    }


def main(argv=None) -> int:
    import run as bench_run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests per second")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--deadlines", default="",
                    help="also report the share answered within each of "
                         "these latencies (seconds, comma-separated)")
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload, False)
    if bench_run.require_chips(cell.chips) is None:
        return bench_run.EXIT_NO_CHIP
    import jax
    jax.config.update("jax_compilation_cache_dir", harness.compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    drv = cell.driver
    t0 = time.perf_counter()
    dep = drv.build(cell.config, args.seed)
    fe = drv.frontend(dep, cell.traffic)
    drv.warm(fe, dep)
    print(f"set-up {time.perf_counter() - t0:.3f} s", flush=True)
    deadlines = [float(d) for d in args.deadlines.split(",") if d]
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        row = sweep_rate(drv, fe, dep, cell.traffic, rate, args.seconds,
                         args.seed + i, deadlines)
        rows.append(row)
        print(json.dumps(row), flush=True)
    fe.close()
    cols = list(rows[0])
    print("| " + " | ".join(cols) + " |")
    print("|" + "---|" * len(cols))
    for r in rows:
        print("| " + " | ".join(f"{r[c]:.4g}" if isinstance(r[c], float)
                                else str(r[c]) for c in cols) + " |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
