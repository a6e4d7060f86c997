"""Kernel micro-benchmarks: interpret-mode timings are NOT TPU performance
(CPU emulation); the derived columns report the structural quantities that
matter on TPU — tiles touched vs skipped (NAP predication saving), VMEM
working set per BlockSpec, and arithmetic intensity.

The `kernels/nap_step/*` section times one full NAP propagation step —
SpMM + exit decision — under all three `spmm_impl` choices side by side:

* ``segment``    — jnp segment-sum + jnp distance reduction;
* ``two_launch`` — Pallas `spmm_block_ell` then `nap_exit` (the propagated
  features round-trip through HBM between the launches);
* ``fused``      — the fused `nap_step` kernel, one grid pass.

Runnable standalone::

    PYTHONPATH=src python -m benchmarks.kernel_bench [--smoke] [--out F]

which also records the rows to a ``BENCH_*.json`` so the perf trajectory
accumulates across commits (CI uploads the smoke variant as an artifact).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import List, Tuple

if __package__ in (None, ""):      # `python benchmarks/kernel_bench.py`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_root, os.path.join(_root, "src")]

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row
from repro.gnn import load_dataset
from repro.gnn.packing import pack_support, step_active_blocks
from repro.gnn.sampler import sample_support
from repro.gnn.store import as_store
from repro.kernels.nap_step import fused_step, two_launch_step
from repro.kernels.spmm import (CB, FB, RB, build_block_ell, pad_features,
                                spmm, spmm_block_ell)

Row = Tuple[str, float, str]


def _time_us(fn, iters: int) -> float:
    """Min wall time over `iters` calls (after one warmup), microseconds."""
    out = fn()
    jax.tree_util.tree_map(
        lambda a: a.block_until_ready() if hasattr(a, "block_until_ready")
        else a, out)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn()
        jax.tree_util.tree_map(
            lambda a: a.block_until_ready() if hasattr(a, "block_until_ready")
            else a, out)
        best = min(best, time.perf_counter() - t0)
    return 1e6 * best


def _random_graph(rng, n: int, deg: int):
    E = n * deg
    src = np.concatenate([rng.integers(0, n, E),
                          np.arange(n)]).astype(np.int32)
    dst = np.concatenate([rng.integers(0, n, E),
                          np.arange(n)]).astype(np.int32)
    key = dst.astype(np.int64) * n + src
    uk = np.unique(key)
    dst, src = (uk // n).astype(np.int32), (uk % n).astype(np.int32)
    coef = rng.random(len(src)).astype(np.float32)
    return src, dst, coef


def _spmm_micro_rows(rng, smoke: bool) -> List[Row]:
    rows: List[Row] = []
    n, deg, f = (256, 4, 128) if smoke else (1024, 8, 256)
    src, dst, coef = _random_graph(rng, n, deg)
    ell = build_block_ell(src, dst, coef, n)
    x = jnp.asarray(pad_features(rng.standard_normal((n, f)), ell.n_pad))
    n_rb = ell.tile_col.shape[0]

    for frac in (1.0, 0.5, 0.1):
        active = jnp.asarray((rng.random(n_rb) < frac).astype(np.int32))
        dt = _time_us(lambda: spmm(ell, x, active),
                      iters=2 if smoke else 3)
        tiles_total = int(ell.valid.sum())
        tiles_live = int(ell.valid[np.asarray(active) != 0].sum())
        vmem_kb = (RB * CB + CB * FB + RB * FB) * 4 / 1024
        ai = (2 * RB * CB * FB) / ((RB * CB + CB * FB + RB * FB) * 4)
        rows.append((
            f"kernels/spmm/active={frac}", dt,
            f"tiles_live={tiles_live}/{tiles_total};"
            f"predicated_saving={1 - tiles_live / tiles_total:.2f};"
            f"vmem_per_step_kb={vmem_kb:.0f};arith_intensity={ai:.1f}"))
    return rows


def _nap_step_rows(rng, smoke: bool) -> List[Row]:
    """One NAP propagation step (SpMM + exit decision) under the three
    spmm_impl choices on identical serving-shaped operands, each a single
    jitted call. The quantity the fusion targets is per-step latency:
    two_launch pays a second kernel launch plus a full (n_pad, F_pad) HBM
    round trip of the propagated features between the SpMM and the
    distance check (and materializes the dense (nb, F_pad) stationary
    state); fused pays none of those — it streams the rank-1 x_inf
    factors. Timings are averages over interleaved rounds (impls
    alternate within each round, so machine drift hits all three
    equally). Interpret-mode wall clock is CPU emulation (it models
    neither HBM nor launch overlap), so the structural columns —
    launches and exit-check operand bytes per step — carry the
    TPU-relevant signal alongside the timing."""
    rows: List[Row] = []
    n, deg, f, nb = (240, 5, 128, 64)       # engine-realistic support
    rounds = 10 if smoke else 50
    src, dst, coef = _random_graph(rng, n, deg)
    ell = build_block_ell(src, dst, coef, n)
    x = jnp.asarray(pad_features(rng.standard_normal((n, f)), ell.n_pad))
    f_pad = x.shape[1]
    c_inf = jnp.asarray(rng.random(nb).astype(np.float32) * 0.1)
    s_inf = jnp.asarray(np.pad(
        rng.standard_normal(f).astype(np.float32), (0, f_pad - f)))
    x_inf = c_inf[:, None] * s_inf[None, :]
    n_rb = ell.tile_col.shape[0]
    active = jnp.ones((n_rb,), jnp.int32)
    nact = jnp.ones((nb, 1), jnp.int32)
    t_s = float(np.sqrt(f))
    tiles = jnp.asarray(ell.tiles)
    tile_col = jnp.asarray(ell.tile_col)
    valid = jnp.asarray(ell.valid)
    sj = jnp.asarray(src)
    dj = jnp.asarray(dst)
    cj = jnp.asarray(coef)
    n_pad = ell.n_pad

    def segment_impl(x):
        out = jax.ops.segment_sum(cj[:, None] * x[sj], dj,
                                  num_segments=n_pad)
        d2 = jnp.sum((out[:nb] - x_inf) ** 2, axis=1, keepdims=True)
        exits = ((nact != 0) & (d2 < t_s * t_s)).astype(jnp.int32)
        blk = exits.reshape(-1, RB).min(axis=1)
        return out, exits, blk

    def two_launch_impl(x):
        return two_launch_step(tiles, tile_col, valid, active, x, c_inf,
                               s_inf, nact, t_s)

    def fused_impl(x):
        return fused_step(tiles, tile_col, valid, active, x, c_inf,
                          s_inf, nact, t_s)

    impls = {"segment": jax.jit(segment_impl),
             "two_launch": jax.jit(two_launch_impl),
             "fused": jax.jit(fused_impl)}

    def timed(fn):
        t0 = time.perf_counter()
        out = fn(x)
        jax.tree_util.tree_map(lambda a: a.block_until_ready(), out)
        return time.perf_counter() - t0

    for fn in impls.values():       # compile + warm
        timed(fn)
        timed(fn)
    total = {name: 0.0 for name in impls}
    for _ in range(rounds):
        for name, fn in impls.items():
            total[name] += timed(fn)
    us = {name: 1e6 * t / rounds for name, t in total.items()}
    # exit-check operand HBM bytes per step on TPU: two_launch re-reads
    # the propagated batch slice + the dense x_inf and re-writes dist/
    # exit/blk; fused streams only the rank-1 factors
    two_bytes = (nb * f_pad * 2 + nb * 3) * 4
    fused_bytes = (nb + f_pad + nb * 2) * 4
    shape = f"n={n};deg={deg};f={f};nb={nb};n_pad={n_pad};f_pad={f_pad}"
    for impl, dt in us.items():
        derived = shape
        if impl == "two_launch":
            derived += f";launches_per_step=2;exit_bytes={two_bytes}"
        if impl == "fused":
            derived += (
                f";launches_per_step=1;exit_bytes={fused_bytes}"
                f";speedup_vs_two_launch="
                f"{us['two_launch'] / max(dt, 1e-9):.2f}x")
        rows.append((f"kernels/nap_step/{impl}", dt, derived))
    return rows


def _support_rows(rng, smoke: bool) -> List[Row]:
    rows: List[Row] = []
    # ---- end-to-end serving operand: vectorized sample -> bucket-padded
    # pack -> kernel with the per-step hop mask (what the compiled engine
    # actually runs). Features sliced to one FB block so interpret mode
    # stays a micro-benchmark.
    g = load_dataset("pubmed-like", scale=0.01 if smoke else 0.02, seed=0)
    batch = rng.choice(g.test_idx, size=16 if smoke else 32, replace=False)
    t_max = 2
    t0 = time.perf_counter()
    sup = sample_support(as_store(g), batch, t_max, 0.5)
    sample_us = 1e6 * (time.perf_counter() - t0)
    x0 = g.features[sup.nodes][:, :FB].astype(np.float32)
    t0 = time.perf_counter()
    packed = pack_support(sup, x0,
                          np.zeros((sup.n_batch, FB), np.float32))
    pack_us = 1e6 * (time.perf_counter() - t0)
    step_act = step_active_blocks(packed.hop_rb, t_max)
    tiles_total = int(packed.valid.sum())
    rows.append((
        "kernels/spmm_support/pack", pack_us,
        f"S={packed.s_real};n_pad={packed.n_pad};"
        f"tb={packed.tiles.shape[1]};density={packed.density:.2f};"
        f"row_overshoot={packed.n_pad / max(packed.s_real, 1):.2f};"
        f"sample_us={sample_us:.0f}"))
    x = jnp.asarray(packed.x0)
    for l in range(1, t_max + 1):
        active = jnp.asarray(step_act[l - 1])
        t0 = time.perf_counter()
        x = spmm_block_ell(jnp.asarray(packed.tiles),
                           jnp.asarray(packed.tile_col),
                           jnp.asarray(packed.valid), active, x)
        x.block_until_ready()
        dt = time.perf_counter() - t0
        live = int(packed.valid[np.asarray(step_act[l - 1]) != 0].sum())
        rows.append((
            f"kernels/spmm_support/step={l}", 1e6 * dt,
            f"tiles_live={live}/{tiles_total};"
            f"hop_mask_saving={1 - live / max(tiles_total, 1):.2f}"))
    return rows


def collect(smoke: bool = False) -> List[Row]:
    rng = np.random.default_rng(0)
    return (_spmm_micro_rows(rng, smoke) + _nap_step_rows(rng, smoke)
            + _support_rows(rng, smoke))


def run() -> list:
    return [csv_row(*r) for r in collect()]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes / few iters (CI parity smoke job)")
    ap.add_argument("--out", default="",
                    help="JSON output path (default BENCH_kernels.json, "
                         "or BENCH_smoke.json with --smoke)")
    args = ap.parse_args()
    out_path = args.out or ("BENCH_smoke.json" if args.smoke
                            else "BENCH_kernels.json")
    rows = collect(smoke=args.smoke)
    print("name,us_per_call,derived")
    for r in rows:
        print(csv_row(*r), flush=True)
    payload = {
        "bench": "kernel_bench",
        "smoke": bool(args.smoke),
        "unix_time": time.time(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "rows": [{"name": n, "us": round(us, 1), "derived": d}
                 for n, us, d in rows],
    }
    with open(out_path, "w") as fh:
        json.dump(payload, fh, indent=2)
        fh.write("\n")
    print(f"# wrote {out_path}")


if __name__ == "__main__":
    main()
