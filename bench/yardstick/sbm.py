"""Degree-corrected stochastic block model graphs at a published size.

The benchmark's own copy of the generator, so that no program change can
move the data a cell runs on. Classes are SBM blocks, node features are
noisy class prototypes, endpoints share a class with probability
`homophily`, and degree propensities follow a Pareto law. Unlike the
program's generator this one hits the published undirected edge count
exactly: it tops up after de-duplication and trims the excess at random.

Everything is drawn from one `np.random.Generator` seeded from the
configuration's fixed graph seed, so every run of a cell serves the same
graph. The endpoint draws are those of `Generator.choice` with a
probability vector, made as its own uniform draws in the same sizes and
order; only the deterministic work around the draws is cheaper (a grid
search of the cumulative table in place of a binary search, one sort by
class in place of a mask per class, blocks on threads), so a graph is the
same, bit for bit, as the one drawn through `choice` that
`bench/tests/test_bench_sbm.py` keeps.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from . import par


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


def _cdf(p: np.ndarray) -> np.ndarray:
    """The table `Generator.choice(len(p), p=p)` searches: the running sum
    of `p` scaled to end at exactly 1."""
    cdf = p.cumsum()
    cdf /= cdf[-1]
    return cdf


def _search_right(cdf: np.ndarray, u: np.ndarray) -> np.ndarray:
    """`cdf.searchsorted(u, side="right")` for keys `u` in [0, 1) and a
    `cdf` that ends at 1. Each key starts at the first entry past the start
    of its cell of a grid of 2**k equal cells (2**k >= len(cdf)) and steps
    right while the entry is <= the key."""
    cells = 1 << max(int(len(cdf) - 1).bit_length(), 1)
    first = cdf.searchsorted(np.arange(cells) / cells, side="right")
    out = np.empty(len(u), np.int64)

    def block(lo, hi):
        x = u[lo:hi]
        idx = first[(x * cells).astype(np.int64)]
        todo = np.flatnonzero(cdf[idx] <= x)
        while todo.size:
            idx[todo] += 1
            todo = todo[cdf[idx[todo]] <= x[todo]]
        out[lo:hi] = idx
    # about eight 8-byte temporaries a key
    par.run(block, par.even(len(u), row_bytes=64))
    return out


def _choice(rng, cdf: np.ndarray, k: int) -> np.ndarray:
    """`rng.choice(len(cdf), size=k, p=p)` for `cdf = _cdf(p)`: the same
    `k` uniform draws from `rng` and the same indices."""
    return _search_right(cdf, rng.random(k))


def _class_tables(theta, labels, c):
    """Per class: its nodes in index order and the cdf of their degree
    propensities (None for a class with no node)."""
    order = np.argsort(labels, kind="stable")
    sorted_theta = theta[order]
    bounds = np.searchsorted(labels[order], np.arange(c + 1))
    out = []
    for cls in range(c):
        t = sorted_theta[bounds[cls]:bounds[cls + 1]]
        out.append((order[bounds[cls]:bounds[cls + 1]],
                    _cdf(t / t.sum()) if len(t) else None))
    return out


def _endpoints(rng, k, cdf, labels, classes, same_class):
    """`k` endpoint pairs drawn by degree propensity. With `same_class` the
    second endpoint is drawn from the first's class: one draw per class in
    class order, for that class's pairs in pair order."""
    u = _choice(rng, cdf, k)
    if not same_class:
        return u, _choice(rng, cdf, k)
    v = np.empty(k, np.int64)
    lu = labels[u]
    c = len(classes)
    # pair positions grouped by class, ascending within each class
    by_class = np.argsort(lu.astype(np.min_scalar_type(c)), kind="stable")
    ends = np.cumsum(np.bincount(lu, minlength=c))
    for cls, (members, pc) in enumerate(classes):
        at = by_class[ends[cls - 1] if cls else 0:ends[cls]]
        if len(at):
            v[at] = members[_choice(rng, pc, len(at))]
    return u, v


def _pair_keys(u: np.ndarray, v: np.ndarray, n: int) -> np.ndarray:
    """`lo * n + hi` of each pair (`lo`, `hi` its smaller and larger end),
    for the pairs with two different ends, in pair order."""
    out = np.empty(len(u), np.int64)

    def block(lo, hi):
        a, b = u[lo:hi], v[lo:hi]
        out[lo:hi] = np.minimum(a, b) * n + np.maximum(a, b)
    par.run(block, par.even(len(u), row_bytes=32))
    return out[u != v]


def _union_sorted(eid: np.ndarray, new: np.ndarray) -> np.ndarray:
    """`np.unique(np.concatenate([eid, new]))` for sorted, distinct `eid`:
    only `new` is sorted from scratch, then the two runs are merged."""
    both = np.concatenate([eid, np.unique(new)])
    both.sort(kind="stable")
    keep = np.empty(len(both), bool)
    keep[:1] = True
    np.not_equal(both[1:], both[:-1], out=keep[1:])
    return both[keep]


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
    cdf = _cdf(theta / theta.sum())
    tables = _class_tables(theta, labels, c)

    eid = np.empty(0, np.int64)
    want = int(edges)
    while len(eid) < edges:
        k_same = int(want * homophily)
        u1, v1 = _endpoints(rng, k_same, cdf, labels, tables, True)
        u2, v2 = _endpoints(rng, want - k_same, cdf, labels, tables, False)
        eid = _union_sorted(eid, np.concatenate([_pair_keys(u1, v1, n),
                                                 _pair_keys(u2, v2, n)]))
        # top up by the deficit, with room for the duplicates it will draw
        want = max(int(1.25 * (edges - len(eid))), 1024)
    # the edges `rng.choice(eid, size=edges, replace=False)` keeps, in key
    # order: eid is sorted, so marking the drawn positions sorts them
    pick = np.zeros(len(eid), bool)
    pick[rng.choice(len(eid), size=int(edges), replace=False)] = True
    eid = eid[pick]
    u, v = np.empty(len(eid), np.int32), np.empty(len(eid), np.int32)

    def split(lo, hi):
        u[lo:hi], v[lo:hi] = np.divmod(eid[lo:hi], n)
    par.run(split, par.even(len(eid), row_bytes=32))
    loops = np.arange(n, dtype=np.int32)
    src = np.concatenate([u, v, loops])
    dst = np.concatenate([v, u, loops])

    protos = rng.standard_normal((c, features)).astype(np.float32)
    noise = rng.standard_normal((n, features))
    feats = np.empty((n, features), np.float32)

    def mix(lo, hi):
        feats[lo:hi] = protos[labels[lo:hi]] + np.float32(
            feature_noise) * noise[lo:hi].astype(np.float32)
    par.run(mix, par.even(n, row_bytes=16 * features))
    del noise

    perm = rng.permutation(n)
    n_test = n // 5
    rest = perm[n_test:]
    n_labeled = max(c * 20, int(0.05 * len(rest)))
    return SBMGraph(n=n, src=src, dst=dst, features=feats, labels=labels,
                    num_classes=c, test_idx=perm[:n_test].astype(np.int32),
                    train_idx=rest[:n_labeled].astype(np.int32),
                    unlabeled_idx=rest[n_labeled:].astype(np.int32))
