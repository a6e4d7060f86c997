"""The control: the reference computed in bfloat16, one precision below
the configuration's float32, put in the program's place, has to come out
as not correct, while the program on the same batches is correct."""
import json
import time

import pytest

import control
import tinybench
from yardstick import harness


@pytest.mark.parametrize("cell", ["tiny.serve", "tiny.offline"])
def test_bfloat16_control_fails_where_the_program_passes(tmp_path, cell):
    root = tinybench.make_root(tmp_path)
    cfg_path = root / "bench/configs/tiny-sgc.json"
    cfg = json.loads(cfg_path.read_text())
    cfg["graph"].update(nodes=3000, edges=15000, features=64)
    cfg_path.write_text(json.dumps(cfg))
    mix_path = root / "bench/traffic/tiny-serve.json"
    mix = json.loads(mix_path.read_text())
    mix["rate_rps"] = 400        # enough answers for a near tie to show
    mix_path.write_text(json.dumps(mix))
    c = harness.find_cell(cell, False, root)
    ctx = harness.Context(cell=c, seed=2**35 + 1, seconds=1.0, trace=False,
                          t_start=time.perf_counter(),
                          out_dir=root / "bench/.out" / cell,
                          log=lambda msg: None, keep_answers=True)
    ctx.out_dir.mkdir(parents=True)
    r = control.readings(c.driver.run(ctx))
    limits = cfg["correct"]
    assert r["compared"] > 0
    assert r["exit_gap"] <= limits["exit_gap"]
    assert r["logit_gap"] <= limits["logit_gap"]
    assert (r["control_exit_gap"] > limits["exit_gap"]
            or r["control_logit_gap"] > limits["logit_gap"])
