"""The benchmark's CPU tests: ``chipbench`` and the program importable
from the checkout's root."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import time  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture()
def run_small(tmp_path):
    """Run a cell once on the CPU at a small size, with a ``fault`` planted
    in its timed path, skipping the look for a chip."""
    from chipbench import harness
    from repro import configs
    from chipbench.tests.small_cells import SMALL, load

    def go(name, seed, fault=None, seconds=2.0):
        cell = load(name)
        key = "train" if cell.traffic["kind"] == "train" else cell.traffic_name
        cell.traffic.update(SMALL[key])
        return harness.run_cell(
            cell, seed, seconds, False, time.monotonic(),
            cfg=configs.get_reduced(cell.config["name"]), require_tpu=False,
            fault=fault, run_dir=tmp_path / "run", compile_cache=False)

    return go
