"""Run one cell of BENCHMARK.json once, on the chip this process finds.

  python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, unless JAX's first device is a TPU
and the cell's chips are present. The last line of stdout is the result
object; the last lines of stderr are the numbers compared, each with its
limit. ``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics from a profiled run.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the checkout's root, not this directory, so that chipbench/trace.py is
# never taken for the standard library's trace module
sys.path[0] = str(ROOT)
if str(ROOT / "src") not in sys.path:
    sys.path.insert(1, str(ROOT / "src"))
# the TPU runtime's own logs would go to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    from chipbench import cells, harness
    cell = cells.load(args.workload)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START)
    checks = " ".join(f"{k}={v['value']} (limit {v['limit']})"
                      for k, v in out["checks"].items())
    print(f"correct={out['correct']} {checks}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
