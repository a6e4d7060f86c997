"""The offline driver at a tiny size on the CPU, correct, and not correct
with the timed path broken underneath."""
import numpy as np
import pytest

import tinybench
from test_bench_drivers import _frozen_step, run_cell


@pytest.fixture
def root(tmp_path):
    return tinybench.make_root(tmp_path)


def _wrong_answer(monkeypatch):
    """A class altered where the offline classifier produces it."""
    from repro.launch import full_graph_infer as fgi
    make = fgi._make_classifier

    def altered(cfg, tmax):
        classify = make(cfg, tmax)

        def wrong(params, exit_order, series):
            out = np.array(classify(params, exit_order, series), copy=True)
            out[0] = (out[0] + 1) % cfg.num_classes
            return out
        return wrong
    monkeypatch.setattr(fgi, "_make_classifier", altered)


def test_offline_driver_runs_and_is_correct(root, capsys):
    out = run_cell(root, "tiny.offline", capsys, seconds=0.5)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"nodes_per_s", "setup_s"}
    assert out["attempted"] % 600 == 0 and out["attempted"] >= 600
    assert out["checks"]["compared"]["value"] == out["attempted"]
    assert not (root / "bench" / ".out" / "tiny.offline" / "ckpt").exists()


@pytest.mark.parametrize("fault", [_wrong_answer, _frozen_step])
def test_offline_faults_are_not_correct(root, capsys, monkeypatch, fault):
    fault(monkeypatch)
    out = run_cell(root, "tiny.offline", capsys, seconds=0.5)
    assert out["correct"] is False
