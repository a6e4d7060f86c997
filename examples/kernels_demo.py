"""Pallas kernel demo: the compiled NAP inference path.

Runs the paper's inference loop with the block-ELL SpMM kernel (NAP row-
block predication) + the fused nap_exit kernel, on a synthetic graph batch,
and verifies it against the pure-numpy host path.

    PYTHONPATH=src python examples/kernels_demo.py

Set ``EXAMPLES_SMOKE=1`` for the scaled-down CI shape.
"""
import os

import jax.numpy as jnp
import numpy as np

from repro.gnn import GNNConfig, load_dataset
from repro.gnn.sampler import sample_support
from repro.gnn.store import InMemoryStore
from repro.kernels.nap_exit import exit_decision
from repro.kernels.spmm import (RB, active_blocks_from_nodes, build_block_ell,
                                pad_features, spmm)

SMOKE = bool(int(os.environ.get("EXAMPLES_SMOKE", "0")))

g = load_dataset("pubmed-like", scale=0.03 if SMOKE else 0.08, seed=0)
cfg = GNNConfig("sgc", g.features.shape[1], g.num_classes, k=4)
batch = g.test_idx[:64 if SMOKE else 256]
T_MIN, T_MAX, T_S = 1, 4, 16.0

# --- build the supporting subgraph + block-ELL operands (store-first:
# the sampler reads through the GraphStore row-gather API)
sup = sample_support(InMemoryStore(g), batch, T_MAX, cfg.r)
nb = sup.n_batch
ell = build_block_ell(sup.src, sup.dst, sup.coef, len(sup))
x = jnp.asarray(pad_features(g.features[sup.nodes], ell.n_pad))
print(f"support: {len(sup)} nodes -> {ell.n_pad} padded, "
      f"{ell.tiles.shape[0]}x{ell.tiles.shape[1]} tiles "
      f"(block density {ell.density:.2f})")

# stationary state (Eq. 7, rank-1 — never materializes Â^inf)
dt = (g.degrees[sup.nodes] + 1).astype(np.float64)
denom = 2.0 * sup.sub_edges + len(sup)
s_sum = ((dt ** (1 - cfg.r))[:, None] * g.features[sup.nodes]).sum(0)
x_inf_nb = jnp.asarray(((dt[:nb] ** cfg.r) / denom)[:, None] * s_sum[None, :])
x_inf = jnp.zeros((ell.n_pad, x.shape[1])).at[:nb, :g.features.shape[1]].set(
    x_inf_nb)

# --- compiled NAP loop: SpMM (predicated) + fused exit decision
# A support node must stay live at step l iff its BFS hop distance is
# within the remaining propagation budget of some still-active batch node;
# batch rows additionally go dead when the node exits. This is the
# block-level shrinking frontier of DESIGN.md §3.
active_batch = np.ones(nb, bool)
exit_order = np.zeros(nb, np.int64)
tiles_touched, tiles_possible = 0, 0
for l in range(1, T_MAX + 1):
    remaining = T_MAX - l
    needed = np.zeros(ell.n_pad, bool)
    needed[:len(sup)] = sup.hop <= remaining
    needed[:nb] |= active_batch          # batch rows live while active
    needed[:nb] &= active_batch | (sup.hop[:nb] <= remaining)
    live = active_blocks_from_nodes(jnp.asarray(needed), ell.n_pad)
    x = spmm(ell, x, live)
    tiles_possible += int(ell.valid.sum())
    tiles_touched += int(ell.valid[np.asarray(live) != 0].sum())
    if l < T_MIN or l == T_MAX:
        continue
    d, exits, _ = exit_decision(x[:nb], x_inf[:nb],
                                jnp.asarray(active_batch), T_S)
    newly = np.asarray(exits) & (exit_order == 0)
    exit_order[newly] = l
    active_batch &= ~np.asarray(exits)
exit_order[exit_order == 0] = T_MAX

# --- verify against the host path
from repro.gnn.nai import _subgraph_spmm
xh = g.features[sup.nodes].astype(np.float32)
needed = np.ones(len(sup), bool)
for l in range(1, T_MAX + 1):
    xh, _ = _subgraph_spmm(sup, xh, needed)
err = float(np.abs(np.asarray(x)[:nb, :g.features.shape[1]] - xh[:nb]).max())
print(f"kernel-vs-host propagation max err @k={T_MAX}: {err:.2e}")
hist = np.bincount(exit_order, minlength=T_MAX + 1)[1:]
print(f"exit-order histogram (T_s={T_S}): {list(hist)}")
print(f"NOTE: with per-block exits the TPU saving appears once whole row "
      f"blocks exit; here {tiles_touched}/{tiles_possible} tiles touched.")
