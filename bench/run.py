"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in `BENCHMARK.json`, its configuration, traffic mix,
driver and metric readers by name (see `yardstick/harness.py`), builds
the deployment, warms every shape the cell uses, measures for `--seconds`
and then checks what the timed path produced against the benchmark's own
reference. Earlier lines report how the run went; the last line of
standard output is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and with ``--trace 1`` ``breakdown``),
then ``checks``: each number compared beside its limit, which the last
lines of standard error repeat.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window. The run needs a TPU with the cell's number of chips: anywhere
else it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                    # noqa: E402
import json                        # noqa: E402
import math                        # noqa: E402
import sys                         # noqa: E402
import traceback                   # noqa: E402
from pathlib import Path           # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

from yardstick import harness      # noqa: E402

EXIT_NO_CHIP = 2


def require_chips(chips: int):
    """The devices, if JAX runs on a TPU with at least `chips` chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU; JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"bench: the cell needs {chips} chips; JAX found "
              f"{len(devices)}", file=sys.stderr)
        return None
    return devices


def _finite(x):
    """JSON has no infinity: a number that never came reads 1e300."""
    return x if math.isfinite(x) else 1e300


def main(argv=None, *, root: Path = ROOT, bench_dir: Path = BENCH,
         require=require_chips, t_start: float = T_START) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.find_cell(args.workload, bool(args.trace), root, bench_dir)
    devices = require(cell.chips)
    if devices is None:
        return EXIT_NO_CHIP
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      harness.compile_cache_dir(root))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    dev = devices[0]
    ctx = harness.Context(
        cell=cell, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_start=t_start,
        out_dir=Path(root) / "bench" / ".out" / cell.name,
        device_kind=dev.device_kind,
        log=lambda msg: print(msg, flush=True))
    ctx.out_dir.mkdir(parents=True, exist_ok=True)
    try:
        record = cell.driver.run(ctx)
        metrics = harness.read_metrics(cell, record, bench_dir)
    except Exception:   # noqa: BLE001 — any failure is a run with no result
        traceback.print_exc()
        return 1

    checks = record["checks"]
    for c in checks:
        c["ok"] = (c["value"] >= c["limit"] if c.get("at_least")
                   else c["value"] <= c["limit"])
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": record["memory_peak_bytes"]}
    out = {"correct": all(c["ok"] for c in checks),
           "attempted": record["attempted"], "failed": record["failed"],
           "metrics": metrics, "device": device}
    tr = record.get("trace")
    if tr is not None:
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["checks"] = {c["name"]: {"value": _finite(c["value"]),
                                 "limit": c["limit"]} for c in checks}
    for c in checks:
        rel = ">=" if c.get("at_least") else "<="
        print(f"check {c['name']} = {c['value']!r} (limit {rel} "
              f"{c['limit']!r}): {'ok' if c['ok'] else 'FAILED'}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
