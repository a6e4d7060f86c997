"""Whole-graph offline jobs through `run_full_graph_infer`, back to back.

The traffic file names only this driver. The jobs run on the cell's
chips: on one, with no mesh; on several, over the program's serving mesh
of that many devices, exchanging rows by the configuration's
``engine.gather_mode``. The backend is the configuration's ``spmm_impl``,
and the program checkpoints after every superstep. Each job is cold: a
fresh checkpoint directory inside the checkout, no resume, deleted after
the job. Jobs run until one ends past `seconds`; the window is the wall
time of those whole jobs, packing, checkpoint writes and classification
included. Set-up runs one job, so the superstep and classifier programs
are in the compilation cache.
"""
from __future__ import annotations

import gc
import shutil
import time

import numpy as np

from yardstick import checks, compiles
from yardstick.deployment import build
from yardstick.measure import Profiler, memory_peak_bytes, span
from yardstick.peaks import peaks


def _job(dep, ocfg, mesh, trace):
    from repro.launch.full_graph_infer import run_full_graph_infer
    shutil.rmtree(ocfg.ckpt_dir, ignore_errors=True)
    try:
        with span("bench.offline_job", trace):
            return run_full_graph_infer(dep.store, dep.gnn, dep.params,
                                        dep.nai, ocfg, mesh=mesh)
    finally:
        shutil.rmtree(ocfg.ckpt_dir, ignore_errors=True)


def run(ctx):
    from repro.launch.full_graph_infer import OfflineConfig
    log = ctx.log
    counter = compiles.counter()
    dep = build(ctx.cell.config, ctx.seed)
    engine = dep.config["engine"]
    chips = ctx.cell.chips
    mesh = None
    sharded = {}
    if chips > 1:
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(chips)
        sharded = {"gather_mode": engine["gather_mode"]}
    ocfg = OfflineConfig(ckpt_dir=str(ctx.out_dir / "ckpt"),
                         spmm_impl=engine["spmm_impl"], resume=False,
                         **sharded)
    _job(dep, ocfg, mesh, False)
    prof = Profiler(ctx.out_dir, ctx.trace)
    jobs = []
    in_window = {}
    gc.collect()
    setup_s = time.perf_counter() - ctx.t_start
    with counter.window(in_window):
        prof.start()
        start = time.perf_counter()
        with span("bench.window", ctx.trace):
            while True:
                jobs.append(_job(dep, ocfg, mesh, ctx.trace))
                if time.perf_counter() - start >= ctx.seconds:
                    break
        wall = time.perf_counter() - start
        prof.stop()
    trace = prof.reduce()
    n = dep.store.n
    stats = [j.stats for j in jobs]
    log(f"window: {len(jobs)} jobs of {n} nodes in {wall:.6f} s")
    for k in ("pack_s", "compute_s", "ckpt_s", "classify_s", "total_s"):
        log(f"  {k} per job: " + ", ".join(f"{s[k]:.6f}" for s in stats))
    log(f"  checkpoint bytes per job: {stats[-1]['ckpt_bytes']}")
    log(f"compiles in window: {in_window}")
    record = {
        "setup_s": setup_s, "seconds": float(ctx.seconds), "chips": chips,
        "wall_s": wall, "jobs": len(jobs), "nodes": n,
        "pack_s": [s["pack_s"] for s in stats],
        "ckpt_s": [s["ckpt_s"] for s in stats],
        "trace": trace, "compiles_in_window": in_window,
        "attempted": n * len(jobs), "failed": 0,
        "memory_peak_bytes": memory_peak_bytes(),
    }
    everyone = np.arange(n)
    batches = [checks.Batch(nodes=everyone, orders=j.exit_orders,
                            preds=j.predictions) for j in jobs]
    del jobs
    gc.collect()
    ref = dep.reference()
    found = checks.compare(ref, batches, whole_graph=True)
    record["checks"] = checks.verdict(found, dep.config["correct"], 0)
    record["work"] = found["work"][:1]
    if trace is not None:
        record["peak"] = peaks(ctx.device_kind)
    if ctx.keep_answers:
        record.update(_deployment=dep, _answers=batches, _whole_graph=True)
    return record
