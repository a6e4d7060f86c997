"""Plain reference of node-adaptive inference (Algorithm 1) with SGC heads.

Independent of the program: it reads only the benchmark's own graph
(`sbm.SBMGraph`) and weights, and computes in float64 with SciPy's sparse
product. The propagation operator is Â = D̃^{r-1} Ã D̃^{-r} with one self loop
per node and degrees without it (paper Eq. 1). Because a batch's support
holds every node within `t_max` hops and the edge coefficients use global
degrees, the batch rows of X^(l) over the support equal the global
(Â^l X)[batch] for l <= t_max; so one global series serves every batch.
What the support changes is the stationary state (Eq. 7), which is rank-1
over the support: x_inf[i] = (d_i+1)^r / (2 m_S + |S|) * sum_{j in S}
(d_j+1)^{1-r} x_j, with m_S the undirected edges inside the support.

A node exits at the first order l in [t_min, t_max) whose Eq. 8 distance
||X^(l)_i - x_inf_i|| is below T_s, else at t_max, and is classified by
the linear head of that order.

Products and row norms run as blocks of rows on threads (`par`), each row
computed as one call would, so the numbers do not depend on the blocks.

``precision="bfloat16"`` is the control: the same computation with every
stored array rounded to bfloat16 (features, coefficients, each step's
output, the stationary state, distances, weights and logits), products
accumulated in float32, as a bfloat16 serving path would compute it.
"""
from __future__ import annotations

import copy
import dataclasses
from typing import Dict, Tuple

import ml_dtypes
import numpy as np
import scipy.sparse as sp

from . import par
from .sbm import SBMGraph


def bf16(a: np.ndarray) -> np.ndarray:
    """Round to bfloat16 and widen back to float32."""
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


@dataclasses.dataclass
class Answers:
    """Per-node readings of one reference over a set of rows."""
    orders: np.ndarray     # (k,) exit order in [t_min, t_max]
    preds: np.ndarray      # (k,) class at the exit order
    dist: np.ndarray       # (t_max + 1, k) Eq. 8 distance per order (row 0 unused)
    logits: np.ndarray     # (t_max + 1, k, C) logits per order (row 0 unused)


def product(A: sp.csr_matrix, x: np.ndarray, blocks: int = None) -> np.ndarray:
    """`A @ x` as blocks of rows on threads. Each row sums its entries in
    the same order as one product does, so the result is bit-identical at
    any number of blocks."""
    out = np.empty((A.shape[0],) + x.shape[1:],
                   np.result_type(A.dtype, x.dtype))

    def block(lo, hi):
        s, e = A.indptr[lo], A.indptr[hi]
        rows = sp.csr_matrix((A.data[s:e], A.indices[s:e],
                              A.indptr[lo:hi + 1] - s),
                             shape=(hi - lo, A.shape[1]))
        out[lo:hi] = rows @ x
    par.run(block, par.even(len(out), blocks, row_bytes=out[:1].nbytes))
    return out


def row_norms(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """`np.linalg.norm(a - b, axis=1)` by blocks of rows on threads."""
    out = np.empty(len(a), np.result_type(a.dtype, b.dtype))

    def block(lo, hi):
        out[lo:hi] = np.linalg.norm(a[lo:hi] - b[lo:hi], axis=1)
    par.run(block, par.even(len(a), row_bytes=a[:1].nbytes))
    return out


class Reference:
    """The reference over one graph. Everything that depends only on the
    graph (the operator and the series X^(l)) is built once; the series
    grows to an order when an order is first asked for. `with_weights`
    shares it between heads."""

    def __init__(self, g: SBMGraph, weights: Dict[int, Tuple[np.ndarray, np.ndarray]],
                 *, r: float, t_min: int, t_max: int, t_s: float = None,
                 precision: str = "float64"):
        if precision not in ("float64", "bfloat16"):
            raise ValueError(f"unknown precision {precision!r}")
        self.g, self.r = g, float(r)
        self.t_min, self.t_max = int(t_min), int(t_max)
        self.t_s = t_s
        self.low = precision == "bfloat16"
        loop = g.src == g.dst
        self.deg = np.bincount(g.dst[~loop], minlength=g.n).astype(np.float64)
        dt = self.deg + 1.0
        self.dt = dt
        # the per-edge dt**e of one power per node: the same numbers
        coef = (dt ** (self.r - 1.0))[g.dst] * (dt ** (-self.r))[g.src]
        self.src, self.dst = g.src, g.dst
        self.nonloop = ~loop
        dtype = np.float32 if self.low else np.float64
        self.A = sp.csr_matrix(
            ((bf16(coef) if self.low else coef).astype(dtype),
             (self.dst, self.src)), shape=(g.n, g.n))
        self._pattern = None
        self.weights = self._cast(weights)
        x = (bf16(g.features) if self.low
             else np.asarray(g.features, np.float64))
        self._series = [x]

    def _cast(self, weights):
        if self.low:
            return {l: (bf16(w), bf16(b)) for l, (w, b) in weights.items()}
        return {l: (np.asarray(w, np.float64), np.asarray(b, np.float64))
                for l, (w, b) in weights.items()}

    def with_weights(self, weights: Dict[int, Tuple[np.ndarray, np.ndarray]]
                     ) -> "Reference":
        """This reference, its operator and series shared, with the heads
        `weights`."""
        out = copy.copy(self)
        out.weights = self._cast(weights)
        return out

    def series(self, l: int) -> np.ndarray:
        """X^(l) = Â^l X over every node (0 <= l <= t_max)."""
        if not 0 <= l <= self.t_max:
            raise ValueError(f"order {l} outside 0..{self.t_max}")
        while len(self._series) <= l:
            x = product(self.A, self._series[-1])
            self._series.append(bf16(x) if self.low else x)
        return self._series[l]

    # ----------------------------------------------------------- supports
    def support_mask(self, batch: np.ndarray) -> np.ndarray:
        """Nodes within t_max hops of `batch` (the sampled support)."""
        if self._pattern is None:
            self._pattern = sp.csr_matrix(
                (np.ones(len(self.src), np.float32), (self.dst, self.src)),
                shape=(self.g.n, self.g.n))
        mask = np.zeros(self.g.n, bool)
        mask[np.asarray(batch, np.int64)] = True
        for _ in range(self.t_max):
            mask = (self._pattern @ mask.astype(np.float32)) > 0
        return mask

    def support_size(self, mask: np.ndarray) -> Tuple[int, int]:
        """(rows, directed edges with self loops) of the induced subgraph."""
        if mask.all():
            return len(mask), len(self.src)
        return int(mask.sum()), int((mask[self.src] & mask[self.dst]).sum())

    def stationary(self, mask: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """Eq. 7 over the support `mask`, at `rows` (k, f)."""
        inside = (self.nonloop if mask.all()
                  else mask[self.src] & mask[self.dst] & self.nonloop)
        denom = 2.0 * (int(inside.sum()) // 2) + int(mask.sum())
        w = np.where(mask, self.dt ** (1.0 - self.r), 0.0)
        x0 = self.series(0)
        if self.low:
            s = bf16(w.astype(np.float32) @ x0)
            c = bf16(self.dt[rows] ** self.r / denom)
            return bf16(c[:, None] * s[None, :])
        s = w @ x0
        c = self.dt[rows] ** self.r / denom
        return c[:, None] * s[None, :]

    # ------------------------------------------------------------ answers
    def answers(self, rows: np.ndarray, mask: np.ndarray) -> Answers:
        if self.t_s is None:
            raise ValueError("set t_s before asking for answers")
        rows = np.asarray(rows, np.int64)
        x_inf = self.stationary(mask, rows)
        k, C = len(rows), self.g.num_classes
        every = k == self.g.n and np.array_equal(rows, np.arange(k))
        dist = np.full((self.t_max + 1, k), np.nan)
        logits = np.zeros((self.t_max + 1, k, C))
        for l in range(1, self.t_max + 1):
            x = self.series(l) if every else self.series(l)[rows]
            w, b = self.weights[l]
            if self.low:
                diff = bf16(x - x_inf)
                dist[l] = bf16(np.sqrt((diff * diff).sum(axis=1)))
                logits[l] = bf16(x @ w + b)
            else:
                dist[l] = row_norms(x, x_inf)
                np.matmul(x, w, out=logits[l])
                logits[l] += b
        orders = np.full(k, self.t_max, np.int64)
        open_ = np.ones(k, bool)
        for l in range(self.t_min, self.t_max):
            now = open_ & (dist[l] < self.t_s)
            orders[now] = l
            open_ &= ~now
        preds = logits[orders, np.arange(k)].argmax(axis=1)
        return Answers(orders=orders, preds=preds, dist=dist, logits=logits)

    def first_step_median(self) -> float:
        """T_s of the deployment: the median Eq. 8 distance after one step
        over the whole graph, in float64."""
        everyone = np.ones(self.g.n, bool)
        x_inf = self.stationary(everyone, np.arange(self.g.n))
        return float(np.median(row_norms(self.series(1), x_inf)))


def gaps(ref: Answers, t_s: float, t_min: int, t_max: int,
         orders: np.ndarray, preds: np.ndarray) -> Tuple[float, float]:
    """The widest gaps by which answers (`orders`, `preds`) depart from the
    reference `ref` on the same rows:

    * exit gap — how far, as a share of T_s, a reference distance lies on
      the wrong side of T_s for the answer's exit order (an order l before
      the exit needs d_l >= T_s, the exit order below t_max needs d < T_s);
    * logit gap — how far the reference logit of the answer's class lies
      below the reference's best logit, at the answer's exit order.
    """
    orders = np.asarray(orders, np.int64)
    preds = np.asarray(preds, np.int64)
    k = len(orders)
    if k == 0:
        return 0.0, 0.0
    bad_order = (orders < t_min) | (orders > t_max)
    if bad_order.any():
        return float("inf"), float("inf")
    exit_gap = np.zeros(k)
    for l in range(t_min, t_max):
        d = ref.dist[l]
        before = orders > l
        exit_gap = np.maximum(exit_gap, np.where(before, (t_s - d) / t_s, 0.0))
        at = orders == l
        exit_gap = np.maximum(exit_gap, np.where(at, (d - t_s) / t_s, 0.0))
    z = ref.logits[orders, np.arange(k)]
    if ((preds < 0) | (preds >= z.shape[1])).any():
        return float(exit_gap.max()), float("inf")
    logit_gap = z.max(axis=1) - z[np.arange(k), preds]
    return float(exit_gap.max()), float(logit_gap.max())
