"""Operation and byte counts against hand counts on a three-node path."""
import numpy as np
import pytest

from yardstick import sbm, work
from yardstick.reference import Reference


def _path_graph():
    # 0 - 1 - 2, both directions plus a self loop each
    src = np.array([0, 1, 1, 2, 0, 1, 2], np.int32)
    dst = np.array([1, 0, 2, 1, 0, 1, 2], np.int32)
    feats = np.arange(6, dtype=np.float32).reshape(3, 2)
    return sbm.SBMGraph(n=3, src=src, dst=dst, features=feats,
                        labels=np.zeros(3, np.int32), num_classes=3,
                        test_idx=np.arange(3, dtype=np.int32),
                        train_idx=np.zeros(0, np.int32),
                        unlabeled_idx=np.zeros(0, np.int32))


def test_counts_by_hand():
    w = work.nai_work(rows=3, edges=7, width=2, steps=2,
                      orders=np.array([1]), t_min=1, classes=3)
    assert w["prop_flops"] == 2 * 7 * 2 * 2                 # 56
    assert w["prop_bytes"] == (2 * 3 * 2 * 4 + 12 * 7) * 2  # 264
    assert w["dist_flops"] == 3 * 1 * 2                     # order 1 only
    assert w["dist_bytes"] == 2 * 1 * 2 * 4
    assert w["cls_flops"] == 2 * 1 * 2 * 3
    assert w["cls_bytes"] == 1 * (2 * 4 + 4) + 2 * 2 * 3 * 4
    flops, nbytes = work.total(w)
    assert flops == 56 + 6 + 12
    assert nbytes == 264 + 16 + 60
    assert work.total(w, ("prop",)) == (56, 264)


def test_least_time_takes_the_binding_peak():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time(1000.0, 50.0, peak) == (10.0, "compute")
    assert work.least_time(100.0, 50.0, peak) == (5.0, "memory")


def test_support_sizes_feed_the_counts():
    g = _path_graph()
    ref = Reference(g, {}, r=0.5, t_min=1, t_max=1, t_s=1.0)
    mask = ref.support_mask(np.array([0]))
    assert mask.tolist() == [True, True, False]
    assert ref.support_size(mask) == (2, 4)      # 0<->1 and two loops
    ref2 = Reference(g, {}, r=0.5, t_min=1, t_max=2, t_s=1.0)
    assert ref2.support_size(ref2.support_mask(np.array([0]))) == (3, 7)


def test_peaks_are_keyed_by_device_kind():
    from yardstick.peaks import peaks
    assert peaks("TPU v5 lite")["flops_per_s"] == 197e12
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("cpu")
