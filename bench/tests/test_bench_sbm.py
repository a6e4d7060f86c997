"""The generator draws the same graph, bit for bit, as the generator it
replaced, which drew its endpoints through `Generator.choice` and masked
the pairs of each class out of all pairs once per class. That generator
is kept here as it was."""
import numpy as np
import pytest

from yardstick import sbm


def _frozen_endpoints(rng, k, theta, labels, c, same_class):
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


def frozen_generate(*, nodes, edges, features, classes, seed, homophily=0.9,
                    power_law=1.6, feature_noise=1.8):
    n, c = int(nodes), int(classes)
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, c, n).astype(np.int32)
    theta = rng.pareto(power_law, n) + 1.0
    theta = np.clip(theta / theta.mean(), 0.05, 50.0)
    eid = np.empty(0, np.int64)
    want = int(edges)
    while len(eid) < edges:
        k_same = int(want * homophily)
        u1, v1 = _frozen_endpoints(rng, k_same, theta, labels, c, True)
        u2, v2 = _frozen_endpoints(rng, want - k_same, theta, labels, c, False)
        u = np.concatenate([u1, u2])
        v = np.concatenate([v1, v2])
        keep = u != v
        lo = np.minimum(u[keep], v[keep]).astype(np.int64)
        hi = np.maximum(u[keep], v[keep]).astype(np.int64)
        eid = np.unique(np.concatenate([eid, lo * n + hi]))
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
    return dict(src=src, dst=dst, features=feats, labels=labels,
                test_idx=perm[:n_test].astype(np.int32),
                train_idx=rest[:n_labeled].astype(np.int32),
                unlabeled_idx=rest[n_labeled:].astype(np.int32))


@pytest.mark.parametrize("homophily", [0.0, 0.9, 1.0])
@pytest.mark.parametrize("size", [
    dict(nodes=40, edges=300, features=3, classes=2, seed=1),
    dict(nodes=600, edges=2400, features=16, classes=4, seed=3),
    dict(nodes=5000, edges=30000, features=8, classes=47, seed=2**40 + 7),
    # enough draws that the grid search runs in blocks on threads
    dict(nodes=90000, edges=200000, features=2, classes=7, seed=11),
])
def test_generator_is_bit_identical_to_the_frozen_one(size, homophily):
    want = frozen_generate(**size, homophily=homophily)
    got = sbm.generate(**size, homophily=homophily)
    for key, a in want.items():
        b = getattr(got, key)
        assert b.dtype == a.dtype and np.array_equal(b, a), key


def test_search_matches_searchsorted_on_ties_and_edges():
    rng = np.random.default_rng(0)
    p = rng.random(1000)
    p[::7] = 0.0                   # runs of equal cdf entries
    cdf = sbm._cdf(p / p.sum())
    keys = np.concatenate([rng.random(200_000), cdf[:-1], [0.0],
                           np.nextafter(cdf[:-1], 0.0), np.arange(8) / 8])
    assert np.array_equal(sbm._search_right(cdf, keys),
                          cdf.searchsorted(keys, side="right"))
    one = sbm._cdf(np.array([1.0]))
    assert np.array_equal(sbm._search_right(one, np.array([0.0, 0.5])),
                          [0, 0])
