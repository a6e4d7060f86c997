"""Batched NAI serving engine (the paper's deployment scenario: streaming
inference over unseen nodes with latency constraints).

Requests (node ids) arrive on a queue; the batch former (`form_batch`)
closes a batch on size OR age — a full `batch_size` immediately, a
partial batch once its oldest request has waited `max_wait_s` — and each
batch runs Algorithm 1. Two stepping entry points: `step()` is the
closed-loop path (serve whatever is queued now; benchmarks submit
pre-formed batches), `poll(now)` is the open-loop path driven by the
deadline-aware front-end (`repro.serving.frontend`) — it respects the
batch former's triggers and advances the pipeline non-blockingly on
quiet ticks. Latency percentiles and the exit-order histogram are
tracked per engine — the quantities a production deployment would alarm
on. Requests carry optional absolute deadlines and an SLO class tag;
the engine itself is deadline-agnostic (goodput accounting lives in the
front-end).

Two serving modes:

* ``mode="host"`` — the faithful numpy path (`infer_batch_host`), with
  real frontier shrinking and MAC accounting.
* ``mode="compiled"`` — the end-to-end compiled path, structured as an
  explicit two-stage software pipeline:

  - **host stage** (`_host_stage`): vectorized support sampling ->
    bucket-padded block-ELL packing into a rotating pool of preallocated
    buffer sets (`pack_support(out=...)`), so the steady state allocates
    no fresh bucket-sized numpy arrays;
  - **device stage** (`_device_stage`): operand transfer plus ONE jitted
    function (Pallas-SpMM masked NAP + per-order classification),
    dispatched asynchronously — the call returns device futures without
    blocking.

  With ``pipeline_depth=1`` the two stages run back to back per batch
  (serial serving, the pre-pipeline behavior). With ``pipeline_depth=2``
  the engine keeps one batch in flight: batch N+1's sampling/packing
  (host stage) overlaps batch N's device compute. Each dispatched batch
  goes to the engine's **completion waiter**, one thread that, in FIFO
  order, blocks until the batch's device results are ready, copies and
  guards them, and delivers the answers on the `Request` objects
  (`prediction`, `exit_order`, ``status="completed"``, `done_s`) and to
  each request's `on_done` callback — so a client that registers one
  gets batch N's answers when the device finishes, not after batch
  N+1's host stage. The engine thread still finalizes batch N once
  batch N+1 has been submitted (stats, records, cache fill, failures):
  `step()` then returns the *previous* batch's requests (and `[]`
  while the pipe fills); `flush()` drains what remains in flight.
  Completion order stays FIFO, so predictions/exit orders are identical
  to serial serving on the same request stream.

  Delivery contract: a request's `done_s` is the moment its answer (or
  its failure) is handed out — its `on_done` callback runs right after
  the stamp, at every depth and in both modes. Failed batches are
  declared on the engine thread at finalize, never by the waiter.

  Each compiled batch keeps one record (`repro.obs`): the spans
  ``serve.host`` (inside it ``serve.sample``, ``serve.gather``,
  ``serve.pack``), ``serve.dispatch`` and ``serve.sync`` add their
  seconds to it, ``hold_s`` is the pipeline hold between dispatch and
  sync, ``queue_wait_s`` sums its requests' waits from arrival to the
  start of the host stage, and ``rows_*``/``edges_*`` count real
  against padded rows and edges. Every span carries the batch's
  sequence number. The hold ends when the device results are ready,
  and ``serve.sync`` is the copy, the guards and the delivery (on the
  waiter when pipelined); ``early`` is 1 when the answers were delivered
  before the engine thread reached the batch's finalize, and ``lead_s``
  is by how long (else 0; always 0 when serial). The record is appended to `batch_timings` and
  published as ``"serve.batch"`` when the batch is finalized.

  Operand shapes are bucketed and held at per-batch-size high-water
  marks, so repeat batches hit the jit compile cache; `jit_stats` counts
  compiles vs hits (alarm on compiles in steady state) and `pack_stats`
  counts pooled-buffer reuses vs allocations (steady state allocates
  zero). The pool rotates ``pipeline_depth + 1`` buffer sets per batch
  bucket, so a buffer refilled by the host stage is never one an
  in-flight batch still reads.

Compiled-mode `spmm_impl` names a registered `PropagationBackend`
(`repro.gnn.backends`): ``"segment"`` (jnp segment-sum), ``"block_ell"``
(Pallas SpMM kernel + separate jnp exit distance), or ``"fused"`` (one
Pallas kernel doing the SpMM, the exit distance, and the next step's
row-block predicate in a single grid pass — no HBM round trip between
matmul and distance check). The backend's declared needs drive both
stages — which operands the host stage packs and which arrays the device
stage ships — so adding an implementation is one registry entry, not
three new dispatch branches. The jitted runner donates its per-batch
operand buffers on backends that implement donation (see
`make_compiled_infer`), so bucketed repeat batches reuse HBM instead of
growing the footprint.

``mesh=`` (any mesh with a ``data`` axis, e.g.
`repro.launch.mesh.make_serving_mesh`) turns on **sharded serving**: the
host stage packs row-partitioned shards (`pack_support(n_shards=D)` —
same static shapes per shard, shard-major superblock round-robin), the
device stage places each operand with its backend-declared
NamedSharding, and the jitted runner executes the NAP loop under
shard_map (live flag psum-reduced) before un-permuting results to the
original batch order. Supports larger than one device's memory split
their packed tiles and rows across the mesh; predictions and exit
orders are bit-identical to single-device serving, and the
pipeline/pool/bucketing machinery is unchanged (zero steady-state
compiles and pack allocations still hold per shard count).

``gather_mode=`` picks the sharded per-step frontier exchange (see
`repro.gnn.backends`): ``"halo"`` (default) packs per-shard halo frames
— each shard's tiles read a (H_pad·CB, f) frame holding exactly the
column blocks they reference, assembled by a static gather — with
``"alltoall"`` the `jax.lax.all_to_all` ragged-exchange variant for
real meshes, and ``"dense"`` the PR-4 full-frontier all_gather
reference. All three are bit-identical; `halo_stats` records the
per-step gathered rows and the halo fraction (halo rows / S_pad) the
benchmark's structural columns are accountable for. Per-order
classification stays row-sharded too: only argmax class ids and exit
orders are gathered off the mesh.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding

from repro.gnn.backends import (BACKENDS, GATHER_MODES, get_backend,
                                normalize_mesh, operand_logical,
                                pack_operands)
from repro.gnn.models import GNNConfig
from repro.gnn.nai import (NAIConfig, infer_batch_host, make_compiled_infer,
                           support_stationary_factors)
from repro.gnn.packing import (CB, PackedSupport, batch_bucket,
                               pack_support, step_active_blocks)
from repro.gnn.propcache import PropCache
from repro.gnn.sampler import sample_support
from repro.gnn.store import as_store
from repro.obs import publish, span
from repro.serving.faults import (InjectedFault, NaNGuardError,
                                  WatchdogTimeout, poison_results)
from repro.sharding.logical import spec

# longest pause between the watchdog's readiness polls (~ the
# interpreter's default thread switch interval)
_WATCH_POLL_MAX_S = 5e-3


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Validated serving-engine configuration.

    Consolidates what used to be a sprawl of `NAIServingEngine` keyword
    arguments into one declarative object (construction-time checks,
    mirroring `NAIConfig.__post_init__`), so per-SLO-class engine
    configs in the front-end are data, not call-site argument lists.
    `NAIServingEngine(..., config=EngineConfig(...))` and the legacy
    kwargs form are equivalent — the kwargs path builds an EngineConfig
    internally, so both get identical validation.
    """
    mode: str = "host"               # "host" (numpy) | "compiled"
    spmm_impl: str = "block_ell"     # registered PropagationBackend name
    gather_mode: str = "halo"        # sharded frontier exchange
    pipeline_depth: int = 1          # 1 = serial, 2 = one batch in flight
    max_wait_s: float = 0.01         # batch former age bound
    donate: Optional[bool] = None    # operand donation (None = backend)
    latency_window: int = 4096       # LatencyRing capacity
    mesh: object = None              # mesh with a "data" axis, or None
    # --- propagated-feature cache (repro.gnn.propcache; 0 = off) ---
    cache_nodes: int = 0             # LRU capacity in cached nodes
    cache_fill: bool = True          # insert batch-row series after serving
    # --- failure-domain isolation (all default off / no-op) ---
    faults: object = None            # FaultPlan schedule, or None
    watchdog_s: Optional[float] = None   # device-sync deadline, None = off
    retry_failed: bool = False       # retry a failed batch once (host path)
    nan_guard: bool = True           # finite/range check on synced results

    def __post_init__(self):
        if self.mode not in ("host", "compiled"):
            raise ValueError(f"unknown mode {self.mode!r}")
        if self.spmm_impl not in BACKENDS:
            raise ValueError(f"unknown spmm_impl {self.spmm_impl!r} "
                             f"(one of {sorted(BACKENDS)})")
        if self.gather_mode not in GATHER_MODES:
            raise ValueError(f"unknown gather_mode {self.gather_mode!r} "
                             f"(one of {GATHER_MODES})")
        if self.pipeline_depth < 1:
            raise ValueError(f"pipeline_depth must be >= 1, got "
                             f"{self.pipeline_depth}")
        if self.pipeline_depth > 1 and self.mode != "compiled":
            raise ValueError("pipelining overlaps host pack with device "
                             "compute; mode='host' has no device stage")
        if self.mesh is not None and self.mode != "compiled":
            raise ValueError("sharded serving (mesh=) requires "
                             "mode='compiled'")
        if self.cache_nodes < 0:
            raise ValueError(f"cache_nodes must be >= 0, got "
                             f"{self.cache_nodes}")
        if self.cache_nodes and self.mode != "compiled":
            raise ValueError("the propagated-feature cache fills from the "
                             "compiled runner's series output; mode='host' "
                             "has none")
        if self.max_wait_s < 0:
            raise ValueError(f"max_wait_s must be >= 0, got "
                             f"{self.max_wait_s}")
        if self.latency_window < 1:
            raise ValueError(f"latency_window must be >= 1, got "
                             f"{self.latency_window}")
        if self.watchdog_s is not None and self.watchdog_s <= 0:
            raise ValueError(f"watchdog_s must be > 0 (or None to "
                             f"disable), got {self.watchdog_s}")
        if self.faults is not None and not callable(
                getattr(self.faults, "injector", None)):
            raise ValueError("faults must be a FaultPlan "
                             "(repro.serving.faults) or None")


@dataclasses.dataclass
class Request:
    node_id: int
    arrival_s: float
    deadline_s: float = float("inf")   # ABSOLUTE completion deadline
    slo_class: str = ""                # routing tier (serving front-end)
    done_s: float = -1.0
    batched_s: float = -1.0            # start of its batch's host stage
    prediction: int = -1
    exit_order: int = -1
    batch_id: int = -1                 # engine batch this completed in
    # terminal lifecycle: every accepted request ends EXACTLY once as
    # "completed" or "failed" (shedding happens before acceptance, at
    # the front-end) — the conservation invariant chaos_bench gates
    status: str = "pending"            # "pending" | "completed" | "failed"
    error: str = ""                    # typed failure cause when failed
    retried: bool = False              # recovered via the reference path
    degraded: bool = False             # demoted by an open circuit breaker
    probe: bool = False                # half-open breaker probe request
    # delivery callback: called once with the request as soon as it is
    # terminal ("completed" or "failed"), right after `done_s` is
    # stamped — `done_s` is the moment of this hand-off. Pipelined
    # compiled serving calls it on the engine's completion waiter
    # thread, often before `step`/`poll` return the request; otherwise
    # on the thread that runs `step`/`poll`/`flush`. It must not call
    # back into the engine; an exception it raises is kept in `error`
    on_done: Optional[Callable[["Request"], None]] = dataclasses.field(
        default=None, compare=False, repr=False)

    @property
    def within_deadline(self) -> bool:
        """Completed in time (the goodput numerator). False while the
        request is still pending."""
        return 0.0 <= self.done_s <= self.deadline_s


class LatencyRing:
    """Fixed-capacity ring of the most recent request latencies.

    Long-running engines append one latency per request forever; an
    unbounded list is a slow memory leak. The ring keeps the latest
    `capacity` samples — enough for stable p50/p95/p99 — at constant
    memory. For short runs (fewer than `capacity` appends) percentiles
    are computed over exactly the same samples an unbounded list would
    hold, so `EngineStats.summary()` is unchanged there.
    """

    def __init__(self, capacity: int = 4096):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._buf = np.zeros(capacity, np.float64)
        self.total_appended = 0

    def append(self, value: float) -> None:
        self._buf[self.total_appended % self.capacity] = value
        self.total_appended += 1

    def __len__(self) -> int:
        return min(self.total_appended, self.capacity)

    def values(self) -> np.ndarray:
        """Current window (order not meaningful once the ring has
        wrapped; percentiles don't care)."""
        return self._buf[:len(self)].copy()

    def __iter__(self):
        return iter(self.values())


@dataclasses.dataclass
class EngineStats:
    served: int = 0
    batches: int = 0
    failed: int = 0        # requests that ended status="failed"
    retried: int = 0       # requests recovered on the reference path
    fill_errors: int = 0   # cache fills that raised (insert skipped)
    latencies: LatencyRing = dataclasses.field(default_factory=LatencyRing)
    exit_hist: Dict[int, int] = dataclasses.field(default_factory=dict)

    def percentile(self, q: float) -> float:
        vals = self.latencies.values()
        return float(np.percentile(vals, q)) if len(vals) else 0.0

    def summary(self) -> Dict[str, float]:
        return {
            "served": self.served,
            "batches": self.batches,
            "failed": self.failed,
            "retried": self.retried,
            "p50_ms": 1e3 * self.percentile(50),
            "p95_ms": 1e3 * self.percentile(95),
            "p99_ms": 1e3 * self.percentile(99),
            "mean_exit_order": (
                sum(k * v for k, v in self.exit_hist.items())
                / max(self.served, 1)),
        }


@dataclasses.dataclass
class _Inflight:
    """One submitted batch that has not been finalized."""
    requests: List[Request]
    inv: np.ndarray          # dedupe inverse map (batch -> unique row)
    nb_real: int             # unique node count (real rows of the result)
    preds_dev: object        # device array futures from the jitted runner
    orders_dev: object
    rec: dict                # the batch's record (repro.obs)
    t_submit: float = 0.0    # wall clock at dispatch (watchdog anchor)
    series_dev: object = None   # (T_max+1, nb, f) batch-row series future
    fill: object = None      # cache fill record (nodes, deps, gv) or None
    # pipeline_depth >= 2: the completion waiter's future, resolving to
    # the delivery time once it has handed out the answers (else None:
    # the engine thread syncs)
    delivery: Optional[Future] = None


class NAIServingEngine:
    def __init__(self, cfg: GNNConfig, nai: NAIConfig, params, graph,
                 *, config: Optional[EngineConfig] = None, **kwargs):
        """`graph` is a `GraphStore` (or a raw `Graph`, wrapped via
        `as_store`). Engine options come either as one validated
        ``config=EngineConfig(...)`` or as the legacy keyword arguments
        (``mode=``, ``spmm_impl=``, ...) — never both; the kwargs path
        just builds an `EngineConfig`, so validation is identical."""
        if config is not None and kwargs:
            raise ValueError(
                f"pass either config=EngineConfig(...) or engine kwargs, "
                f"not both (got kwargs {sorted(kwargs)})")
        ec = config if config is not None else EngineConfig(**kwargs)
        mesh = normalize_mesh(ec.mesh) if ec.mesh is not None else None
        mode, gather_mode = ec.mode, ec.gather_mode
        spmm_impl, pipeline_depth = ec.spmm_impl, ec.pipeline_depth
        self.config = ec
        self.cfg = cfg
        self.nai = nai
        self.params = params
        self.store = as_store(graph)
        self.graph = graph
        self.max_wait_s = ec.max_wait_s
        self.mode = mode
        self.spmm_impl = spmm_impl
        self.mesh = mesh
        self.n_shards = int(mesh.shape["data"]) if mesh is not None else 1
        # the frontier exchange only exists across shards — a degenerate
        # mesh serves the plain single-device path
        self.gather_mode = gather_mode if self.n_shards > 1 else "dense"
        # per-step exchange footprint of the worst batch seen (sharded
        # engines only; serving_bench's structural halo columns)
        self.halo_stats: Dict[str, float] = {
            "gather_rows_per_step": 0, "halo_rows": 0, "s_pad": 0,
            "halo_frac": 0.0}
        self.pipeline_depth = pipeline_depth
        self.queue: Deque[Request] = deque()
        self.stats = EngineStats(latencies=LatencyRing(ec.latency_window))
        # propagated-feature cache (sharded: partitioned so each shard's
        # cache holds rows its shard owns — see PropCache.n_shards)
        self.cache: Optional[PropCache] = (
            PropCache(ec.cache_nodes, nai.t_max, n_shards=self.n_shards)
            if ec.cache_nodes else None)
        self.cache_fill = ec.cache_fill
        # SpMM row accounting: support rows sampled vs rows actually
        # packed for device propagation (the cache's compute saving)
        self.row_stats: Dict[str, int] = {"rows_support": 0,
                                          "rows_packed": 0}
        # failure-domain isolation knobs (EngineConfig, all off by default)
        self.watchdog_s = ec.watchdog_s
        self.retry_failed = ec.retry_failed
        self.nan_guard = ec.nan_guard
        self._faults = (ec.faults.injector()
                        if ec.faults is not None else None)
        # compiled-path state: jitted runner + bucket high-water marks
        # keyed by padded batch size
        # -> (s_bucket, tb_bucket, e_bucket, h_bucket, hb_bucket, k_bucket)
        self.jit_stats: Dict[str, int] = {"compiles": 0, "hits": 0}
        self.pack_stats: Dict[str, int] = {"allocs": 0, "reuses": 0}
        # per-batch records (stage seconds and counters), bounded
        self.batch_timings: Deque[Dict[str, float]] = deque(maxlen=1024)
        self._batch_seq = 0      # sequence number of the next batch
        self._runner = None
        self._bucket_hwm: Dict[int, Tuple[int, ...]] = {}
        self._seen_keys: set = set()
        self._inflight: Deque[_Inflight] = deque()
        # completion waiter (pipeline_depth >= 2), started with the first
        # pipelined batch and shut down by close()
        self._waiter: Optional[ThreadPoolExecutor] = None
        # rotating pack-buffer pool: bucket -> pipeline_depth + 1 slots
        self._pack_pool: Dict[int, List[Optional[PackedSupport]]] = {}
        self._pool_idx: Dict[int, int] = {}
        self._backend = None
        self._shardings = None
        if mode == "compiled":
            self._backend = get_backend(spmm_impl)
            if self.mesh is not None:
                # backend, mesh, gather mode, and operand keys are fixed
                # for the engine's lifetime — build the per-operand
                # NamedShardings once, off the per-batch dispatch path
                logical = dict(operand_logical(self._backend,
                                               self.gather_mode,
                                               seeds=self.cache
                                               is not None),
                               x0=("row_shard", None),
                               x_inf=("row_shard", None))
                self._shardings = {
                    name: NamedSharding(self.mesh,
                                        spec(*dims, mesh=self.mesh))
                    for name, dims in logical.items()}
            self._runner = make_compiled_infer(
                cfg, nai, spmm_impl=spmm_impl, donate=ec.donate, mesh=self.mesh,
                gather_mode=self.gather_mode,
                return_series=self.cache is not None)
            self._cls_params = {
                l: {k: jnp.asarray(v) for k, v in p.items()}
                for l, p in params["cls"].items()}

    def jit_cache_size(self) -> int:
        """Shapes traced by the compiled runner (0 in host mode)."""
        return self._runner._cache_size() if self._runner is not None else 0

    @property
    def fault_stats(self) -> Optional[Dict]:
        """Per-stage injected-fault tallies (None without a FaultPlan)."""
        return self._faults.summary() if self._faults is not None else None

    @property
    def cache_stats(self) -> Dict[str, float]:
        """Propagated-feature-cache counters (hits/misses/stale/fills/
        evictions/hit_rate) merged with the engine's SpMM row accounting.
        With the cache off only the row counters appear (and
        rows_packed == rows_support)."""
        d: Dict[str, float] = dict(self.row_stats)
        if self.cache is not None:
            d.update(self.cache.stats())
        return d

    def reset_stats(self) -> None:
        """Zero the serving counters — request stats, per-batch timings,
        row accounting, and the cache's hit/miss/fill counters — without
        touching serving state (cache CONTENTS, pack pools, high-water
        marks, and jit/pack structural counters all survive, so a warm
        engine stays warm and steady-state compile accounting stays
        meaningful across a reset)."""
        self.stats = EngineStats(
            latencies=LatencyRing(self.config.latency_window))
        self.batch_timings.clear()
        self.row_stats = {"rows_support": 0, "rows_packed": 0}
        if self.cache is not None:
            self.cache.reset_stats()

    def close(self) -> None:
        """Drain in-flight work, stop the completion waiter, then release
        the store's OS resources (fd/maps for `MmapStore`). Idempotent —
        front-ends sharing one store across per-class engines close it
        once per engine."""
        self.flush()
        if self._waiter is not None:
            self._waiter.shutdown()
            self._waiter = None
        self.store.close()

    def pooled_bytes(self) -> Dict[str, int]:
        """Largest host bytes of each pooled pack operand (``tiles``,
        ``x0``, ``src``, ...) over every buffer set the pool holds — what
        one batch ships to the device per operand, at its bucket high
        water mark. Empty in host mode."""
        sizes: Dict[str, int] = {}
        for slots in self._pack_pool.values():
            for p in slots:
                if p is None:
                    continue
                for f in dataclasses.fields(p):
                    a = getattr(p, f.name)
                    if isinstance(a, np.ndarray):
                        sizes[f.name] = max(sizes.get(f.name, 0), a.nbytes)
        return sizes

    @property
    def donate_argnums(self) -> tuple:
        """Argnums the jitted runner donates (empty in host mode or on
        backends without donation support)."""
        return (self._runner._donate_argnums
                if self._runner is not None else ())

    # ------------------------------------------------------- host stage
    def _host_stage(self, nodes: np.ndarray, rec: dict):
        """Sample the support and pack it into a pooled buffer set,
        plus the static per-step row-block predicate for the Pallas
        impls. `nodes` must be duplicate-free. Pure host work — no jax
        calls, and no full-graph arrays: everything reads through the
        store's row-gather view API, so an `MmapStore` only pages in the
        support's rows. The sampler, feature gather and packing spans
        and the real/padded row and edge counts go to the batch's `rec`.

        Returns ``(packed, step_active, fill)``: `fill` is the
        propagated-feature-cache fill record (batch nodes, dependency
        node set, mutation clock at sample time) for `_finalize_oldest`
        to insert once the batch's series has synced — or None with the
        cache off."""
        store, cfg, nai = self.store, self.cfg, self.nai
        be = self._backend
        seq = rec["batch"]
        with span(rec, "serve.sample", batch=seq):
            sup = sample_support(store, nodes, nai.t_max, cfg.r,
                                 cache=self.cache)
        nb = sup.n_batch
        n_hit = int(sup.hit.sum()) if sup.hit is not None else 0
        self.row_stats["rows_support"] += len(sup)
        self.row_stats["rows_packed"] += len(sup) - n_hit
        with span(rec, "serve.gather", batch=seq):
            x0 = store.gather_features(sup.nodes).astype(np.float32)
            # dense x_inf is built from the f32 factors so the fused
            # kernel (which streams the factors and multiplies in f32) is
            # bit-consistent with the dense block_ell/segment distance;
            # in fused mode the dense matrix is never materialized at
            # all — a zero-column placeholder carries the batch-row count
            c_inf, s_inf = support_stationary_factors(store, sup, x0, cfg.r)
            c_inf = c_inf.astype(np.float32)
            s_inf = s_inf.astype(np.float32)
            if be.uses_dense_x_inf:
                x_inf = c_inf[:, None] * s_inf[None, :]
            else:
                x_inf = np.zeros((nb, 0), np.float32)
        with span(rec, "serve.pack", batch=seq):
            packed, step_active = self._pack(sup, x0, x_inf, c_inf, s_inf)
        rec.update(rows_real=packed.s_real, rows_pad=packed.n_pad,
                   edges_real=len(sup.src), edges_pad=packed.src.size)
        fill = None
        if self.cache is not None and self.cache_fill:
            # the full support node set is the conservative dependency
            # cone of every batch row's series (see PropCache.fill)
            fill = (nodes, sup.nodes, sup.graph_version)
        return packed, step_active, fill

    def _pack(self, sup, x0, x_inf, c_inf, s_inf):
        """Pack the sampled support into the bucket's next pooled buffer
        set, raise the bucket's high-water marks and count the shape as
        a jit hit or compile. Returns ``(packed, step_active)``."""
        nai, be = self.nai, self._backend
        nb_bucket = batch_bucket(sup.n_batch, self.n_shards)
        hwm = self._bucket_hwm.get(nb_bucket, (0, 0, 0, 0, 0, 0))
        slots = self._pack_pool.setdefault(
            nb_bucket, [None] * (self.pipeline_depth + 1))
        idx = self._pool_idx.get(nb_bucket, 0)
        packed = pack_support(sup, x0, x_inf, nb_bucket=nb_bucket,
                              s_bucket=hwm[0], tb_bucket=hwm[1],
                              e_bucket=hwm[2],
                              build_tiles=be.uses_tiles,
                              build_edges=be.uses_edges,
                              x_inf_factors=(c_inf, s_inf)
                              if be.uses_factors else None,
                              out=slots[idx], n_shards=self.n_shards,
                              halo=self.gather_mode != "dense",
                              h_bucket=hwm[3], hb_bucket=hwm[4],
                              seeds=(sup.hit, sup.seed_vals)
                              if self.cache is not None else None,
                              k_bucket=hwm[5])
        slots[idx] = packed
        self._pool_idx[nb_bucket] = (idx + 1) % len(slots)
        self.pack_stats["reuses" if packed.reused else "allocs"] += 1
        self._bucket_hwm[nb_bucket] = (
            max(hwm[0], packed.n_pad), max(hwm[1], packed.tiles.shape[1]),
            max(hwm[2], packed.src.shape[-1]),
            max(hwm[3], packed.n_halo_pad),
            max(hwm[4], packed.halo_send_pad),
            max(hwm[5], packed.seed_pad))
        if self.mesh is not None:
            # per-step exchange footprint (structural: what the compiled
            # gather materializes vs the true boundary vs dense S_pad)
            halo_on = packed.halo_src_shard is not None
            grows = (packed.n_halo_pad * CB if halo_on else packed.n_pad)
            hrows = packed.halo_rows if halo_on else packed.n_pad
            hs = self.halo_stats
            hs["gather_rows_per_step"] = max(hs["gather_rows_per_step"],
                                             grows)
            hs["halo_rows"] = max(hs["halo_rows"], hrows)
            hs["s_pad"] = max(hs["s_pad"], packed.n_pad)
            hs["halo_frac"] = max(hs["halo_frac"],
                                  packed.halo_frac if halo_on else 1.0)

        key = packed.shape_key(self.spmm_impl)
        if key in self._seen_keys:
            self.jit_stats["hits"] += 1
        else:
            self._seen_keys.add(key)
            self.jit_stats["compiles"] += 1
        step_active = (step_active_blocks(packed.hop_rb, nai.t_max)
                       if be.uses_tiles else None)
        return packed, step_active

    # ----------------------------------------------------- device stage
    def _device_stage(self, packed: PackedSupport,
                      step_active: Optional[np.ndarray]):
        """Transfer operands and dispatch the jitted runner. Returns
        device futures (predictions, exit orders) WITHOUT blocking —
        jax dispatch is asynchronous, so host work for the next batch can
        proceed while the device computes.

        Operand construction is backend-driven (`pack_operands`): no
        per-impl branches. Sharded (mesh set), every operand is placed
        with its backend-declared NamedSharding, so each device receives
        only its row shard — the point at which a support larger than one
        device's memory becomes servable."""
        operands = pack_operands(self._backend, packed, step_active)
        if self.mesh is not None:
            sh = self._shardings

            def put(name, a):
                return jax.device_put(np.asarray(a), sh[name])

            operands = {k: put(k, v) for k, v in operands.items()}
            x0 = put("x0", packed.x0)
            x_inf = put("x_inf", packed.x_inf)
        else:
            operands = {k: jnp.asarray(v) for k, v in operands.items()}
            x0 = jnp.asarray(packed.x0)
            x_inf = jnp.asarray(packed.x_inf)
        out = self._runner(self._cls_params, operands, x0, x_inf)
        # with the cache on, the runner also returns the batch-row series
        # (the fill source); pad the cache-off path to the same arity
        return out if self.cache is not None else (*out, None)

    def _watchdog_sync(self, fl: _Inflight) -> None:
        """Bound the device sync: poll `is_ready` until the results are
        complete or `watchdog_s` has elapsed since dispatch, then raise
        `WatchdogTimeout` — the batch is declared hung and failed, and
        the pipeline slot it held is free again (re-armed). With the
        watchdog off (None) this returns immediately and the sync
        blocks, exactly the pre-watchdog behavior.

        The pause between polls doubles from 100 us up to
        `_WATCH_POLL_MAX_S`, so a long device stage wakes the polling
        thread a few hundred times a second, not ten thousand: on the
        completion waiter each wake-up takes the interpreter lock from
        the engine thread's host stage."""
        wd = self.watchdog_s
        if wd is None:
            return
        deadline = fl.t_submit + wd
        pause = 1e-4
        for dev in (fl.preds_dev, fl.orders_dev):
            ready = getattr(dev, "is_ready", None)
            if ready is None:
                continue
            while not ready():
                if time.perf_counter() >= deadline:
                    raise WatchdogTimeout(
                        f"device sync not ready {wd * 1e3:.0f} ms after "
                        f"dispatch; batch of {len(fl.requests)} declared "
                        f"hung")
                time.sleep(pause)
                pause = min(2.0 * pause, _WATCH_POLL_MAX_S)

    def _guard_results(self, preds: np.ndarray, orders: np.ndarray,
                       nb_real: int) -> None:
        """Fail the batch if the device returned garbage: non-finite
        values (NaN/Inf logits surviving to the argmax) or out-of-range
        class ids / exit orders. Guards VALUES only — a passing batch's
        results are byte-identical to the unguarded path."""
        if not self.nan_guard:
            return
        p, o = preds[:nb_real], orders[:nb_real]
        for what, a in (("predictions", p), ("exit orders", o)):
            if a.dtype.kind == "f" and not np.all(np.isfinite(a)):
                raise NaNGuardError(
                    f"non-finite {what} from the device stage")
        if p.size:
            lo, hi = int(p.min()), int(p.max())
            if lo < 0 or hi >= self.cfg.num_classes:
                raise NaNGuardError(
                    f"prediction ids [{lo}, {hi}] outside "
                    f"[0, {self.cfg.num_classes})")
            olo, ohi = int(o.min()), int(o.max())
            if olo < 1 or ohi > self.nai.t_max:
                raise NaNGuardError(
                    f"exit orders [{olo}, {ohi}] outside "
                    f"[1, {self.nai.t_max}]")

    def _fail_batch(self, batch: List[Request], err: Exception
                    ) -> List[Request]:
        """Terminal handling for a batch whose stage raised: the failure
        domain is THIS batch only — nothing here touches the queue, the
        pipeline, or other in-flight batches. With `retry_failed` the
        batch gets one graceful-degradation attempt on the reference
        host path (`infer_batch_host`, the numpy `segment` semantics —
        always available, never compiled) before being declared failed."""
        if self.retry_failed and not any(r.retried for r in batch):
            for r in batch:
                r.retried = True
            try:
                nodes = np.asarray([r.node_id for r in batch])
                uniq, inv = np.unique(nodes, return_inverse=True)
                p_u, o_u, _, _, _ = infer_batch_host(
                    self.cfg, self.nai, self.params, self.store, uniq)
            except Exception as retry_err:   # noqa: BLE001 — isolation
                err = retry_err
            else:
                self.stats.retried += len(batch)
                self._complete(batch, p_u[inv], o_u[inv],
                               time.perf_counter())
                self._account(batch)
                return batch
        msg = f"{type(err).__name__}: {err}"
        for r in batch:
            r.status = "failed"
            r.error = msg
            r.done_s = time.perf_counter()
        self._notify(batch)
        self.stats.failed += len(batch)
        return batch

    def _fetch(self, fl: _Inflight):
        """Copy a batch's ready device results to the host, guard them,
        and map them back to the batch's requests. Raises on a sync or
        guard failure. Returns ``(preds, orders)`` in request order."""
        preds_a = np.asarray(fl.preds_dev)
        orders_a = np.asarray(fl.orders_dev)
        self._guard_results(preds_a, orders_a, fl.nb_real)
        return (preds_a[:fl.nb_real][fl.inv],
                orders_a[:fl.nb_real][fl.inv])

    def _await_and_deliver(self, fl: _Inflight) -> float:
        """Sync one batch and deliver its answers: block until the device
        results are ready — a plain `block_until_ready`, which releases
        the interpreter lock, or the watchdog's bounded wait when armed —
        then copy, guard and hand the answers to the batch's requests
        (`_complete`). Pipelined, the completion waiter runs this on its
        own thread in FIFO order; serial, the engine thread runs it at
        finalize. A batch whose sync or guards fail gets nothing
        delivered: the error is raised to the finalize. Returns the
        delivery time."""
        rec = fl.rec
        if self.watchdog_s is None:
            jax.block_until_ready((fl.preds_dev, fl.orders_dev))
        else:
            self._watchdog_sync(fl)
        rec["hold_s"] = time.perf_counter() - fl.t_submit
        with span(rec, "serve.sync", batch=rec["batch"]):
            preds, orders = self._fetch(fl)
            done = time.perf_counter()
            self._complete(fl.requests, preds, orders, done)
        return done

    def _fill_cache(self, fl: _Inflight) -> None:
        """Insert a delivered batch's series into the propagated-feature
        cache (no-op without a fill record). Called only after the guards
        pass — a poisoned/hung batch must not seed future batches. Steps
        1..T_max of a batch row are exact global values (hop 0, full
        budget), so the whole series is insertable. The answers are out
        already, so a fill that raises only skips the insert (each row
        it did insert is whole) and is counted in `stats.fill_errors`."""
        if fl.fill is None:
            return
        batch_nodes, dep_nodes, gv = fl.fill
        try:
            series = np.asarray(fl.series_dev)
            self.cache.fill(
                self.store, batch_nodes,
                series[1:, :fl.nb_real].transpose(1, 0, 2), dep_nodes, gv)
        except Exception:   # noqa: BLE001 — the batch is already answered
            self.stats.fill_errors += 1

    def _finalize_oldest(self) -> List[Request]:
        """Finalize the oldest in-flight batch: take its delivered answers,
        then keep stats, the record, the cache fill and the returned
        requests. FIFO, so completion order matches submission order
        regardless of pipeline depth. Pipelined, the completion waiter
        has synced the batch and delivered its answers, usually long
        before this point, and this joins it; serial, the sync and the
        delivery run here. A sync failure, watchdog trip, or guard trip
        fails ONLY this batch — the slot is released either way."""
        fl = self._inflight.popleft()
        rec = fl.rec
        reached = time.perf_counter()
        try:
            done = (self._await_and_deliver(fl) if fl.delivery is None
                    else fl.delivery.result())
        except Exception as e:   # noqa: BLE001 — batch-level isolation
            return self._fail_batch(fl.requests, e)
        rec["early"] = int(done < reached)
        rec["lead_s"] = max(reached - done, 0.0)
        self._fill_cache(fl)
        self.batch_timings.append(rec)
        publish("serve.batch", rec)
        self._account(fl.requests)
        return fl.requests

    def _complete(self, batch: List[Request], preds, orders,
                  done: float) -> None:
        """Hand a batch's answers to its requests — what a client reads,
        and what its `on_done` callback is given. Pipelined, the
        completion waiter calls this; `_account` then counts the batch
        on the engine thread."""
        for r, p, o in zip(batch, preds, orders):
            r.done_s = done
            r.prediction = int(p)
            r.exit_order = int(o)
            r.status = "completed"
        self._notify(batch)

    @staticmethod
    def _notify(batch: List[Request]) -> None:
        """Call the terminal requests' `on_done` callbacks. A callback
        that raises is the client's fault, not the batch's: its error is
        kept on its request and the others still run."""
        for r in batch:
            if r.on_done is not None:
                try:
                    r.on_done(r)
                except Exception as e:   # noqa: BLE001 — client code
                    r.error = f"on_done: {type(e).__name__}: {e}"

    def _account(self, batch: List[Request]) -> None:
        """Count a delivered batch into the engine's stats."""
        bid = self.stats.batches
        for r in batch:
            r.batch_id = bid
            self.stats.latencies.append(r.done_s - r.arrival_s)
            self.stats.exit_hist[r.exit_order] = \
                self.stats.exit_hist.get(r.exit_order, 0) + 1
        self.stats.served += len(batch)
        self.stats.batches += 1

    def _validate_node_id(self, node_id) -> int:
        """Reject an out-of-range id at SUBMIT time with a clear error.
        Unvalidated, a bad id fails deep in the sampler with an opaque
        index error — and takes its whole batch down with it."""
        nid = int(node_id)
        if not 0 <= nid < self.store.n:
            raise ValueError(
                f"node id {nid} out of range for store "
                f"{self.store.name!r} with n={self.store.n} nodes "
                f"(valid ids are 0..{self.store.n - 1})")
        return nid

    def submit(self, node_ids, now: Optional[float] = None) -> None:
        now = time.perf_counter() if now is None else now
        # validate the whole call before enqueuing any of it, so a bad
        # id rejects atomically instead of half-submitting
        nids = [self._validate_node_id(nid)
                for nid in np.atleast_1d(node_ids)]
        for nid in nids:
            self.queue.append(Request(nid, now))

    def submit_request(self, req: Request) -> None:
        """Enqueue a pre-built request (the front-end path: deadline and
        SLO class already stamped by `repro.serving.frontend`)."""
        self._validate_node_id(req.node_id)
        self.queue.append(req)

    def form_batch(self, now: Optional[float] = None, *,
                   force: bool = False) -> List[Request]:
        """Deadline-aware batch former: close a batch on size OR age,
        whichever comes first. A full `batch_size` closes immediately;
        a partial batch closes only once its oldest request has waited
        `max_wait_s` — and then it closes UNCONDITIONALLY, taking
        everything queued (up to batch_size). The latency bound takes
        priority over batch fill: there is no minimum-fill guard (the
        old `batch_size // 4` gate held post-deadline batches hostage to
        fill — and degenerated them to size 1 whenever batch_size <= 3).
        Returns [] while neither trigger has fired.

        `now` defaults to the wall clock; pass an explicit timestamp to
        drive the former on a virtual clock (deterministic tests/parity
        replays). `force=True` (the closed-loop benchmark path and
        `flush`) closes whatever is queued immediately."""
        if not self.queue:
            return []
        if not force:
            now = time.perf_counter() if now is None else now
            aged = now - self.queue[0].arrival_s >= self.max_wait_s
            if len(self.queue) < self.nai.batch_size and not aged:
                return []           # neither size nor age has closed it
        batch: List[Request] = []
        while self.queue and len(batch) < self.nai.batch_size:
            batch.append(self.queue.popleft())
        return batch

    def _advance(self, opportunistic: bool = False) -> List[Request]:
        """Finalize only batches already past the pipeline depth — the
        empty-queue path must NOT drain the pipeline (a momentarily
        empty queue under bursty arrivals is exactly when overlap
        matters; a full drain is a sync barrier that silently degrades
        pipeline_depth=2 to serial). `flush()` stays the explicit drain.

        `opportunistic=True` (the front-end's `poll`) additionally
        finalizes in-flight batches whose device results are ALREADY
        complete and delivered — `jax.Array.is_ready` and the waiter's
        future make that a non-blocking check, so they leave the
        pipeline promptly during arrival lulls without ever stalling on
        unfinished device work. Their answers wait for neither path: the
        completion waiter delivers them as soon as the device results
        are ready (`_await_and_deliver`)."""
        done: List[Request] = []
        while len(self._inflight) >= self.pipeline_depth:
            done += self._finalize_oldest()
        if opportunistic:
            while self._inflight:
                # no is_ready attribute means the results are already
                # host-materialized (plain arrays), i.e. trivially ready
                # — treating that as NOT ready parks the batch below
                # pipeline_depth where poll() can never finalize it.
                # Device results leave once ready AND delivered by the
                # waiter, so poll never waits on the copy either
                head = self._inflight[0]
                ready = getattr(head.preds_dev, "is_ready", None)
                if ready is not None and not (ready()
                                              and head.delivery.done()):
                    break
                done += self._finalize_oldest()
        # watchdog re-arm: a hung head batch must not wedge open-loop
        # serving (poll never blocks, so without this check a
        # never-ready future parks below pipeline_depth forever) —
        # finalize it now; _watchdog_sync declares it failed immediately
        # since its deadline has already passed
        if self.watchdog_s is not None:
            while (self._inflight
                   and time.perf_counter() - self._inflight[0].t_submit
                   >= self.watchdog_s):
                done += self._finalize_oldest()
        return done

    def _inject_host_faults(self) -> None:
        """Host-stage injection point (`slow` then `host`); called once
        per served batch so a plan's event counters align with batch
        indices. No-op without a FaultPlan."""
        if self._faults is None:
            return
        spec = self._faults.fire("slow")
        if spec is not None and spec.delay_s > 0.0:
            time.sleep(spec.delay_s)
        if self._faults.fire("host") is not None:
            raise InjectedFault("injected host-stage failure")

    def _serve_batch(self, batch: List[Request]) -> List[Request]:
        seq = self._batch_seq
        self._batch_seq += 1
        t0 = time.perf_counter()
        for r in batch:
            r.batched_s = t0
        nodes = np.asarray([r.node_id for r in batch])
        # dedupe per batch (client retries): the sampler requires
        # duplicate-free batches — duplicated rows would double-count in
        # the stationary state and skew every exit distance
        uniq, inv = np.unique(nodes, return_inverse=True)
        if self.mode == "host":
            try:
                self._inject_host_faults()
                p_u, o_u, _, _, _ = infer_batch_host(
                    self.cfg, self.nai, self.params, self.store, uniq)
            except Exception as e:   # noqa: BLE001 — batch isolation
                return self._fail_batch(batch, e)
            self._complete(batch, p_u[inv], o_u[inv], time.perf_counter())
            self._account(batch)
            return batch
        rec = {"batch": seq, "n": len(batch),
               "queue_wait_s": sum(t0 - r.arrival_s for r in batch)}
        try:
            with span(rec, "serve.host", batch=seq):
                self._inject_host_faults()
                packed, step_active, fill = self._host_stage(uniq, rec)
            with span(rec, "serve.dispatch", batch=seq):
                if (self._faults is not None
                        and self._faults.fire("device") is not None):
                    raise InjectedFault("injected device-stage failure")
                preds_dev, orders_dev, series_dev = self._device_stage(
                    packed, step_active)
                preds_dev, orders_dev = poison_results(
                    self._faults, preds_dev, orders_dev)
        except Exception as e:   # noqa: BLE001 — batch-level isolation:
            # a stage failure takes down THIS batch only; in-flight
            # batches and the queue are untouched, and _advance keeps
            # the pipeline moving
            return self._fail_batch(batch, e) + self._advance()
        fl = _Inflight(batch, inv, packed.nb_real, preds_dev, orders_dev,
                       rec, t_submit=time.perf_counter(),
                       series_dev=series_dev, fill=fill)
        if self.pipeline_depth > 1:
            if self._waiter is None:
                self._waiter = ThreadPoolExecutor(
                    1, thread_name_prefix="nai-waiter")
            fl.delivery = self._waiter.submit(self._await_and_deliver, fl)
        self._inflight.append(fl)
        done: List[Request] = []
        while len(self._inflight) >= self.pipeline_depth:
            done += self._finalize_oldest()
        return done

    def step(self) -> List[Request]:
        """Closed-loop step: serve whatever is queued RIGHT NOW (up to
        batch_size), without waiting on the batch former's size/age
        triggers — callers on this path (benchmarks, run_until_drained)
        submit pre-formed batches. Returns completed requests; with
        pipeline_depth > 1 those belong to an EARLIER batch (or none
        while the pipeline fills/idles) — call `flush()` after the last
        `step()` to drain the in-flight tail. An empty queue only
        advances the pipeline (no drain barrier)."""
        batch = self.form_batch(force=True)
        if not batch:
            return self._advance()
        return self._serve_batch(batch)

    def poll(self, now: Optional[float] = None) -> List[Request]:
        """Open-loop serving step (the front-end path): dispatch a batch
        only if size OR age has closed one (`form_batch`), otherwise
        advance the pipeline non-blockingly — finalizing batches past
        the pipeline depth plus any whose device results are already
        complete. Never blocks on unfinished device work and never
        serves a partial batch before its age bound."""
        batch = self.form_batch(now)
        if not batch:
            return self._advance(opportunistic=True)
        return self._serve_batch(batch)

    def flush(self) -> List[Request]:
        """Sync and complete every in-flight batch (no-op when serial)."""
        done: List[Request] = []
        while self._inflight:
            done += self._finalize_oldest()
        return done

    def run_until_drained(self) -> EngineStats:
        while self.queue:
            self.step()
        self.flush()
        return self.stats
