"""Degree-corrected stochastic block model graphs at a published size.

The benchmark's own copy of the generator, so that no program change can
move the data a cell runs on. Classes are SBM blocks, node features are
noisy class prototypes, endpoints share a class with probability
`homophily`, and degree propensities follow a Pareto law. Unlike the
program's generator this one hits the published undirected edge count
exactly: it tops up after de-duplication and trims the excess at random.

Everything is drawn from one `np.random.Generator` seeded from the
configuration's fixed graph seed, so every run of a cell serves the same
graph.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class SBMGraph:
    n: int
    src: np.ndarray        # (E,) int32, both directions plus one self loop per node
    dst: np.ndarray        # (E,) int32
    features: np.ndarray   # (n, f) float32
    labels: np.ndarray     # (n,) int32
    num_classes: int
    test_idx: np.ndarray   # (n // 5,) int32
    train_idx: np.ndarray
    unlabeled_idx: np.ndarray

    @property
    def num_edges(self) -> int:
        """Undirected edges without self loops."""
        return (len(self.src) - self.n) // 2


def _endpoints(rng, k, theta, labels, c, same_class):
    n = len(theta)
    p = theta / theta.sum()
    u = rng.choice(n, size=k, p=p)
    if not same_class:
        return u, rng.choice(n, size=k, p=p)
    v = np.empty(k, np.int64)
    order = np.argsort(labels, kind="stable")
    sorted_theta = theta[order]
    bounds = np.searchsorted(labels[order], np.arange(c + 1))
    for cls in range(c):
        m = labels[u] == cls
        lo, hi = bounds[cls], bounds[cls + 1]
        if not m.any():
            continue
        pc = sorted_theta[lo:hi] / sorted_theta[lo:hi].sum()
        v[m] = order[lo + rng.choice(hi - lo, size=int(m.sum()), p=pc)]
    return u, v


def generate(*, nodes: int, edges: int, features: int, classes: int,
             seed: int, homophily: float = 0.9, power_law: float = 1.6,
             feature_noise: float = 1.8) -> SBMGraph:
    """A graph with exactly `nodes` nodes and `edges` undirected edges."""
    n, c = int(nodes), int(classes)
    if edges > n * (n - 1) // 2:
        raise ValueError(f"{edges} edges do not fit {n} nodes")
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, c, n).astype(np.int32)
    theta = rng.pareto(power_law, n) + 1.0
    theta = np.clip(theta / theta.mean(), 0.05, 50.0)

    eid = np.empty(0, np.int64)
    want = int(edges)
    while len(eid) < edges:
        k_same = int(want * homophily)
        u1, v1 = _endpoints(rng, k_same, theta, labels, c, True)
        u2, v2 = _endpoints(rng, want - k_same, theta, labels, c, False)
        u = np.concatenate([u1, u2])
        v = np.concatenate([v1, v2])
        keep = u != v
        lo = np.minimum(u[keep], v[keep]).astype(np.int64)
        hi = np.maximum(u[keep], v[keep]).astype(np.int64)
        eid = np.unique(np.concatenate([eid, lo * n + hi]))
        # top up by the deficit, with room for the duplicates it will draw
        want = max(int(1.25 * (edges - len(eid))), 1024)
    eid = np.sort(rng.choice(eid, size=int(edges), replace=False))
    u, v = (eid // n).astype(np.int32), (eid % n).astype(np.int32)
    loops = np.arange(n, dtype=np.int32)
    src = np.concatenate([u, v, loops])
    dst = np.concatenate([v, u, loops])

    protos = rng.standard_normal((c, features)).astype(np.float32)
    feats = protos[labels] + np.float32(feature_noise) * rng.standard_normal(
        (n, features)).astype(np.float32)

    perm = rng.permutation(n)
    n_test = n // 5
    rest = perm[n_test:]
    n_labeled = max(c * 20, int(0.05 * len(rest)))
    return SBMGraph(n=n, src=src, dst=dst, features=feats, labels=labels,
                    num_classes=c, test_idx=perm[:n_test].astype(np.int32),
                    train_idx=rest[:n_labeled].astype(np.int32),
                    unlabeled_idx=rest[n_labeled:].astype(np.int32))
