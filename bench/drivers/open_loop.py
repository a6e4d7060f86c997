"""Open-loop serving: single-node requests at a fixed Poisson rate through
`ServingFrontend` into `NAIServingEngine`, for `seconds`, then a drain.

Traffic parameters (``traffic/<mix>.json``):

* ``rate_rps``   — offered requests per second, fixed in the file;
* ``slo_class``  — ``name``, ``deadline_s``, ``max_wait_s``, ``queue_depth``;
* ``gap_seed``   — seed of the one set of inter-arrival gaps every run
  permutes.

Requests go to nodes drawn uniformly from the test split. Each request is
stamped at its intended arrival time, so a stalled loop cannot hide its
queueing delay. Set-up builds the deployment and serves
one batch at every batch-size bucket the batch former can close (8, 16,
24, ... up to the batch size) with the test nodes of highest degree, so
each bucket's shapes reach their high-water mark and compile before the
window.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from yardstick import arrivals, checks, compiles
from yardstick.deployment import build
from yardstick.measure import Profiler, memory_peak_bytes, percentile, span
from yardstick.peaks import peaks

DRAIN_LIMIT_S = 60.0
IDLE_SLEEP_S = 0.0005


def frontend(dep, traffic):
    from repro.serving.engine import EngineConfig
    from repro.serving.frontend import SLOClass, ServingFrontend
    e = dep.config["engine"]
    c = traffic["slo_class"]
    engine = EngineConfig(mode=e["mode"], spmm_impl=e["spmm_impl"],
                          pipeline_depth=e["pipeline_depth"],
                          cache_nodes=e["cache_nodes"],
                          max_wait_s=c["max_wait_s"])
    cls = SLOClass(c["name"], dep.nai, deadline_s=c["deadline_s"],
                   max_wait_s=c["max_wait_s"], queue_depth=c["queue_depth"])
    return ServingFrontend(dep.gnn, dep.params, dep.store, [cls],
                           engine=engine)


def bucket_sizes(batch_size: int):
    """Every batch-region bucket a batch of 1..batch_size nodes pads to."""
    from repro.gnn.packing import batch_bucket
    return sorted({batch_bucket(n) for n in range(1, batch_size + 1)})


def warm(fe, dep):
    """Serve one batch per bucket, with the highest-degree test nodes."""
    from repro.serving.engine import Request
    eng = next(iter(fe.engines.values()))
    test = dep.graph.test_idx
    deg = np.bincount(dep.graph.dst, minlength=dep.graph.n)[test]
    top = test[np.argsort(-deg, kind="stable")]
    for b in bucket_sizes(dep.nai.batch_size):
        now = time.perf_counter()
        for n in top[:b]:
            eng.submit_request(Request(int(n), now))
        eng.step()
    eng.flush()
    fe.reset_stats()


def serve_window(fe, times, nodes, slo, trace=False):
    """Offer `nodes` at `times` (seconds from the start), stamping each
    request at its intended arrival. Returns (requests or None where shed, intended and submitted times in
    seconds from the start, start, close)."""
    n = len(times)
    reqs = [None] * n
    submitted = np.zeros(n)
    i = 0
    start = time.perf_counter()
    with span("bench.window", trace):
        while i < n:
            now = time.perf_counter() - start
            if times[i] <= now:
                with span("bench.submit", trace):
                    while i < n and times[i] <= now:
                        reqs[i] = fe.submit(int(nodes[i]), slo,
                                            now=start + times[i])
                        submitted[i] = now
                        i += 1
            with span("bench.frontend", trace):
                done = fe.step()
            idle = times[i] - (time.perf_counter() - start) if i < n else 0
            if not done and idle > 2 * IDLE_SLEEP_S:
                with span("bench.idle", trace):
                    time.sleep(IDLE_SLEEP_S)
    close = time.perf_counter()
    return reqs, submitted, start, close


def drain(fe):
    limit = time.perf_counter() + DRAIN_LIMIT_S
    while fe.pending() and time.perf_counter() < limit:
        fe.step()


def answered_batches(reqs):
    """The completed requests grouped by engine batch."""
    by = {}
    for r in reqs:
        if r is not None and r.status == "completed":
            by.setdefault(r.batch_id, []).append(r)
    return [checks.Batch(nodes=np.array([r.node_id for r in rs]),
                         orders=np.array([r.exit_order for r in rs]),
                         preds=np.array([r.prediction for r in rs]))
            for _, rs in sorted(by.items())]


def run(ctx):
    from repro.gnn.backends import get_backend
    traffic, seed, log = ctx.cell.traffic, ctx.seed, ctx.log
    counter = compiles.counter()
    dep = build(ctx.cell.config, seed)
    fe = frontend(dep, traffic)
    eng = next(iter(fe.engines.values()))
    slo = traffic["slo_class"]["name"]
    warm(fe, dep)

    times = arrivals.poisson_schedule(traffic["rate_rps"], ctx.seconds,
                                      seed=seed, gap_seed=traffic["gap_seed"])
    nodes = arrivals.uniform_nodes(dep.graph.test_idx, len(times), seed=seed)
    prof = Profiler(ctx.out_dir, ctx.trace)
    jit0, shapes0 = eng.jit_stats["compiles"], eng.jit_cache_size()
    in_window = {}
    gc.collect()
    setup_s = time.perf_counter() - ctx.t_start
    with counter.window(in_window):
        prof.start()
        reqs, submitted, start, close = serve_window(fe, times, nodes, slo,
                                                     ctx.trace)
        prof.stop()
    drain(fe)
    trace = prof.reduce()

    deadline_hits = 0
    latency = []
    shed = failed = unanswered = 0
    for r, t in zip(reqs, times):
        if r is None:
            shed += 1
            latency.append(math.inf)
        elif r.status == "completed":
            latency.append(r.done_s - (start + t))
            deadline_hits += r.within_deadline
        else:
            latency.append(math.inf)
            failed += r.status == "failed"
            unanswered += 1
    st = eng.stats
    timings = list(eng.batch_timings)
    shipped = ("x0", "x_inf",
               *get_backend(dep.config["engine"]["spmm_impl"]).operand_logical)
    h2d = sum(v for k, v in eng.pooled_bytes().items() if k in shipped)
    late50, late_max = arrivals.lateness(times, submitted)
    log(f"window: {len(times)} offered at {traffic['rate_rps']} req/s over "
        f"{ctx.seconds} s, closed {close - start:.6f} s after start; "
        f"generator late p50 {late50 * 1e3:.3f} ms, max {late_max * 1e3:.3f} ms")
    log(f"outcomes: {deadline_hits} within deadline, {shed} shed, "
        f"{unanswered} unanswered ({failed} of them failed); "
        f"{st.batches} batches, {st.served} served")
    (p50, beyond50), (p95, beyond95) = (percentile(latency, 50),
                                        percentile(latency, 95))
    log(f"latency samples: {len(latency)} (every offered request); "
        f"p50 {float(p50)!r} s with {beyond50} beyond it, p95 {float(p95)!r} s with "
        f"{beyond95} beyond it")
    log(f"compiles in window: {in_window} (engine new shapes "
        f"{eng.jit_stats['compiles'] - jit0}, traced shapes "
        f"{eng.jit_cache_size() - shapes0})")

    record = {
        "setup_s": setup_s, "seconds": float(ctx.seconds),
        "chips": ctx.cell.chips, "latency_s": latency,
        "deadline_hits": deadline_hits,
        "offered": len(times), "served": st.served, "batches": st.batches,
        "batch_size": dep.nai.batch_size,
        "host_s": [t["host_s"] for t in timings], "h2d_bytes": h2d,
        "trace": trace, "compiles_in_window": in_window,
        "attempted": len(times), "failed": shed + unanswered,
        "memory_peak_bytes": memory_peak_bytes(),
    }
    batches = answered_batches(reqs)
    fe.close()
    del fe, eng, reqs
    gc.collect()

    ref = dep.reference()
    found = checks.compare(ref, batches)
    record["checks"] = checks.verdict(found, dep.config["correct"], unanswered)
    record["work"] = found["work"]
    if trace is not None:
        record["peak"] = peaks(ctx.device_kind)
    if ctx.keep_answers:
        record.update(_deployment=dep, _answers=batches)
    return record
