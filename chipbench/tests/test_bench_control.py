"""The controls at a size the CPU holds: the reference in fp8 put in the
program's place fails the training cell's limits, and ranks served tokens
worse than the program does; the planted faults read as PERF.md says."""
import pytest

from chipbench import control
from repro import configs

from chipbench.tests.small_cells import SMALL, load


def small(name, **over):
    cell = load(name)
    key = "train" if cell.traffic["kind"] == "train" else cell.traffic_name
    cell.traffic.update(SMALL[key], **over)
    return cell, configs.get_reduced(cell.config["name"])


def test_training_control_and_faults_fail_the_limits():
    cell, cfg = small("mamba2-370m.train_stream")
    lim = {k: v["limit"] for k, v in cell.limits["numbers"].items()}
    out = control.train_readings(cell, 3, cfg)
    for case in ("control_fp8", "state_unchanged"):
        assert any(out[case][k] > lim[k] for k in lim), (case, out[case], lim)
    assert out["state_unchanged"]["update_leaf_gap"] == pytest.approx(1.0)


def test_serving_control_ranks_worse_than_the_program():
    cell, cfg = small("hymba-1.5b.serve_long_output", max_new=16)
    out = [control.serve_readings(cell, s, cfg) for s in (1, 2)]
    assert max(o["control_fp8"] for o in out) > max(o["program"] for o in out)
