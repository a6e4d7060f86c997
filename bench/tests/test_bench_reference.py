"""The benchmark's own generator, arrivals and reference, against the
program's host Algorithm 1 at a tiny size; and the one reference of a
deployment, its products in threaded row blocks, against the two it
replaced."""
import numpy as np
import pytest
import scipy.sparse as sp

from yardstick import arrivals, sbm
from yardstick.reference import Reference, gaps, product

SIZE = dict(nodes=600, edges=2400, features=16, classes=4)


@pytest.fixture(scope="module")
def graph():
    return sbm.generate(**SIZE, seed=3)


def test_generator_hits_the_published_counts(graph):
    g = graph
    assert g.n == 600 and g.num_edges == 2400
    loops = g.src == g.dst
    assert loops.sum() == g.n
    pairs = set(zip(g.src[~loops].tolist(), g.dst[~loops].tolist()))
    assert len(pairs) == 2 * 2400                       # no duplicates
    assert all((v, u) in pairs for u, v in pairs)       # symmetric
    assert g.features.shape == (600, 16) and g.features.dtype == np.float32
    again = sbm.generate(**SIZE, seed=3)
    assert np.array_equal(again.src, g.src)
    assert np.array_equal(again.features, g.features)
    assert len(g.test_idx) == 120


def test_schedule_gives_every_seed_the_same_work():
    a = arrivals.poisson_schedule(200.0, 5.0, seed=1, gap_seed=0)
    b = arrivals.poisson_schedule(200.0, 5.0, seed=2**40 + 3, gap_seed=0)
    assert len(a) == len(b) == 1000
    assert a[-1] == pytest.approx(5.0) and b[-1] == pytest.approx(5.0)
    assert np.allclose(np.sort(np.diff(a, prepend=0)),
                       np.sort(np.diff(b, prepend=0)))
    assert not np.allclose(a, b)
    n1 = arrivals.uniform_nodes(np.arange(50), 100, seed=7)
    assert np.array_equal(n1, arrivals.uniform_nodes(np.arange(50), 100,
                                                     seed=7))
    z = arrivals.zipf_requests(np.arange(50), 1000, exponent=1.0, seed=1)
    counts = np.bincount(z, minlength=50)
    assert counts.max() > 5 * np.median(counts)


def _program_answers(graph, weights, t_s, batch):
    from repro.gnn.graph import Graph
    from repro.gnn.models import GNNConfig
    from repro.gnn.nai import NAIConfig, infer_batch_host
    from repro.gnn.store import as_store
    g = graph
    store = as_store(Graph(n=g.n, src=g.src, dst=g.dst, features=g.features,
                           labels=g.labels, num_classes=g.num_classes,
                           train_idx=g.train_idx,
                           unlabeled_idx=g.unlabeled_idx,
                           test_idx=g.test_idx))
    cfg = GNNConfig("sgc", g.features.shape[1], g.num_classes, k=3,
                    mlp_layers=1)
    params = {"cls": {l: {"w0": w, "b0": b} for l, (w, b) in weights.items()}}
    nai = NAIConfig(t_s=t_s, t_min=1, t_max=3, batch_size=len(batch))
    preds, orders, _, _, _ = infer_batch_host(cfg, nai, params, store, batch)
    return preds, orders


def test_reference_agrees_with_the_host_algorithm(graph):
    rng = np.random.default_rng(0)
    f, c = SIZE["features"], SIZE["classes"]
    weights = {l: (rng.standard_normal((f, c)).astype(np.float32) / 4,
                   0.1 * rng.standard_normal(c).astype(np.float32))
               for l in (1, 2, 3)}
    t_s = Reference(graph, {}, r=0.5, t_min=1, t_max=1).first_step_median()
    ref = Reference(graph, weights, r=0.5, t_min=1, t_max=3, t_s=t_s)
    for batch in (np.sort(rng.choice(graph.test_idx, 16, replace=False)),
                  np.sort(graph.test_idx[:64])):
        a = ref.answers(batch, ref.support_mask(batch))
        preds, orders = _program_answers(graph, weights, t_s, batch)
        assert (orders == a.orders).mean() >= 0.98
        assert (preds == a.preds).mean() >= 0.98
        e, lg = gaps(a, t_s, 1, 3, orders, preds)
        assert e < 1e-5 and lg < 1e-5
        assert set(np.unique(a.orders)) <= {1, 2, 3}


def test_gaps_measure_how_far_an_answer_is_wrong(graph):
    rng = np.random.default_rng(1)
    f, c = SIZE["features"], SIZE["classes"]
    weights = {l: (rng.standard_normal((f, c)), np.zeros(c)) for l in (1, 2, 3)}
    ref = Reference(graph, weights, r=0.5, t_min=1, t_max=3, t_s=1.0)
    ref.t_s = float(np.median(ref.answers(graph.test_idx, np.ones(graph.n, bool)).dist[1]))
    rows = graph.test_idx[:40]
    a = ref.answers(rows, ref.support_mask(rows))
    assert gaps(a, ref.t_s, 1, 3, a.orders, a.preds) == (0.0, 0.0)
    wrong = a.preds.copy()
    wrong[0] = (wrong[0] + 1) % c
    z = a.logits[a.orders[0], 0]
    assert gaps(a, ref.t_s, 1, 3, a.orders, wrong)[1] == pytest.approx(
        z.max() - z[wrong[0]])
    late = a.orders.copy()
    i = int(np.flatnonzero(a.orders < 3)[0])
    late[i] += 1
    d = a.dist[a.orders[i], i]
    assert gaps(a, ref.t_s, 1, 3, late, a.preds)[0] == pytest.approx(
        (ref.t_s - d) / ref.t_s)
    bad = a.orders.copy()
    bad[0] = 0
    assert gaps(a, ref.t_s, 1, 3, bad, a.preds) == (float("inf"),) * 2


@pytest.mark.parametrize("blocks", [1, 3, 8])
def test_threaded_product_is_bit_equal_to_one_product(graph, blocks):
    ref = Reference(graph, {}, r=0.5, t_min=1, t_max=1)
    x = np.asarray(graph.features, np.float64)
    assert np.array_equal(product(ref.A, x, blocks=blocks), ref.A @ x)
    x32 = graph.features
    assert np.array_equal(product(ref.A.astype(np.float32), x32,
                                  blocks=blocks),
                          ref.A.astype(np.float32) @ x32)


class _TwoReferences:
    """The float64 reference as it was, built twice per deployment: once
    at t_max 1 for T_s, once with the heads for the check; every product
    one SciPy call, the series built whole at construction."""

    def __init__(self, g, weights, *, r, t_min, t_max, t_s=None):
        self.g, self.r, self.t_s = g, r, t_s
        self.t_min, self.t_max = t_min, t_max
        loop = g.src == g.dst
        self.dt = np.bincount(g.dst[~loop], minlength=g.n) + 1.0
        coef = self.dt[g.dst] ** (r - 1.0) * self.dt[g.src] ** (-r)
        self.src, self.dst = g.src.astype(np.int64), g.dst.astype(np.int64)
        self.nonloop = ~loop
        A = sp.csr_matrix((coef, (self.dst, self.src)), shape=(g.n, g.n))
        self.weights = {l: (np.asarray(w, np.float64),
                            np.asarray(b, np.float64))
                        for l, (w, b) in weights.items()}
        x = np.asarray(g.features, np.float64)
        self.series = [x]
        for _ in range(t_max):
            x = A @ x
            self.series.append(x)

    def stationary(self, mask, rows):
        inside = mask[self.src] & mask[self.dst] & self.nonloop
        denom = 2.0 * (int(inside.sum()) // 2) + int(mask.sum())
        s = np.where(mask, self.dt ** (1.0 - self.r), 0.0) @ self.series[0]
        return (self.dt[rows] ** self.r / denom)[:, None] * s[None, :]

    def first_step_median(self):
        x_inf = self.stationary(np.ones(self.g.n, bool), np.arange(self.g.n))
        return float(np.median(np.linalg.norm(self.series[1] - x_inf, axis=1)))

    def answers(self, rows, mask):
        x_inf = self.stationary(mask, rows)
        k, C = len(rows), self.g.num_classes
        dist = np.full((self.t_max + 1, k), np.nan)
        logits = np.zeros((self.t_max + 1, k, C))
        for l in range(1, self.t_max + 1):
            x = self.series[l][rows]
            w, b = self.weights[l]
            dist[l] = np.linalg.norm(x - x_inf, axis=1)
            logits[l] = x @ w + b
        orders = np.full(k, self.t_max, np.int64)
        open_ = np.ones(k, bool)
        for l in range(self.t_min, self.t_max):
            now = open_ & (dist[l] < self.t_s)
            orders[now] = l
            open_ &= ~now
        preds = logits[orders, np.arange(k)].argmax(axis=1)
        return orders, preds, dist, logits


@pytest.mark.parametrize("size", [SIZE, dict(nodes=70000, edges=300000,
                                              features=8, classes=5)])
def test_one_reference_reads_as_the_two_it_replaced(size):
    g = sbm.generate(**size, seed=21)
    rng = np.random.default_rng(3)
    f, c = size["features"], size["classes"]
    weights = {l: (rng.standard_normal((f, c)), rng.standard_normal(c))
               for l in (1, 2, 3)}
    t_s = _TwoReferences(g, {}, r=0.5, t_min=1, t_max=1).first_step_median()
    old = _TwoReferences(g, weights, r=0.5, t_min=1, t_max=3, t_s=t_s)
    base = Reference(g, {}, r=0.5, t_min=1, t_max=3)
    base.t_s = base.first_step_median()
    assert base.t_s == t_s
    new = base.with_weights(weights)
    assert new.series(1) is base.series(1)
    everyone = np.ones(g.n, bool)
    for rows, mask in [(np.arange(g.n), everyone),
                       (np.sort(g.test_idx[:64]), None)]:
        if mask is None:
            mask = new.support_mask(rows)
        a = new.answers(rows, mask)
        orders, preds, dist, logits = old.answers(rows, mask)
        assert np.array_equal(a.orders, orders)
        assert np.array_equal(a.preds, preds)
        assert np.array_equal(a.dist, dist, equal_nan=True)
        assert np.array_equal(a.logits, logits)
