"""``run.py`` refuses, quickly and with no result, where there is no TPU or
no program."""
import os
import shutil
import subprocess
import sys
import time

from chipbench import cells

ARGS = ["--workload", "mamba2-370m.train_stream", "--seed", str(2**31 + 5),
        "--seconds", "1", "--trace", "0"]


def run(cwd, **env_over):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_over)
    t0 = time.monotonic()
    p = subprocess.run([sys.executable, "chipbench/run.py", *ARGS], cwd=cwd,
                       env=env, capture_output=True, text=True, timeout=120)
    return p, time.monotonic() - t0


def test_exits_nonzero_without_a_tpu():
    p, took = run(cells.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
    assert took < 60


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(cells.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".run", "__pycache__"))
    p, _ = run(tmp_path, PYTHONPATH="")
    assert p.returncode != 0 and p.stdout.strip() == ""
