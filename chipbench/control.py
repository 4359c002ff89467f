"""The controls of the ``correct`` comparison, for reading on the chip at
each cell's size and for the tests at a small one.

  python chipbench/control.py --workload <cell> --seeds <n> [<n> ...]

Training: the plain reference put in the program's place and computed in
fp8 (the control), with half of each batch left out, and with a step that
leaves its state unchanged, each compared with the float32 reference by
the cell's numbers. Serving: on the same prompts and served tokens, the
gap of the token that the fp8 reference puts first, beside the gap of the
program's served token. Prints one JSON line per seed; the benchmark's own
runs never run this.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
if str(ROOT / "src") not in sys.path:
    sys.path.insert(1, str(ROOT / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def doc_batches(traffic: dict, seed: int, steps: int):
    """``steps`` batches packed from the seed's documents in order, as the
    loader packs a stream."""
    import itertools
    from chipbench import workload
    lo, hi = traffic["doc_words"]
    docs = (workload.document(seed, i, lo, hi).encode() for i in itertools.count())
    return workload.pack(docs, traffic["batch"], traffic["seq"], steps)


def train_readings(cell, seed: int, cfg) -> dict:
    """The cell's numbers for the control and for the faults a training
    cell can have, each against the float32 reference."""
    from repro.optim import OptConfig
    from chipbench import reference
    from chipbench.families import common as c
    t = cell.traffic
    opt = OptConfig(**t["opt"])
    batches = doc_batches(t, seed, 3)
    ref = reference.train_steps(cfg, seed, batches, opt)
    out = {}
    for name, kw in (("control_fp8", {"nm": c.CONTROL}),
                     ("half_batch", {"rows": t["batch"] // 2}),
                     ("state_unchanged", {"frozen": True})):
        x = reference.train_steps(cfg, seed, batches, opt, **kw)
        out[name] = reference.compare_train(x["losses"], x["g1"], x["d3"], ref)
    return out


def serve_readings(cell, seed: int, cfg) -> dict:
    """The widest gap of the program's served tokens (prefill, then greedy
    decode through the server's own programs at the cell's batch) and of
    the fp8 control's first choices, under the float32 reference."""
    import gc
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import Model
    from repro.runtime.serve_loop import make_decode_fn, make_prefill_fn
    from chipbench import reference, weights, workload
    from chipbench.families import common as c
    t = cell.traffic
    B, P, new = t["batch"], t["prompt_len"], t["max_new"]
    k = t["check_requests"]
    reqs = workload.requests(t, seed, 30.0)[:B]
    prompts = [[workload.BOS] + list(r["prompt"].encode()) for r in reqs]
    toks = np.full((B, P), workload.PAD, np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p[-P:])] = p[-P:]
    model = Model(cfg)
    params = weights.make(model.abstract_params(), seed)
    logits, cache = make_prefill_fn(model, P + new)(params, {"tokens": jnp.asarray(toks)})
    decode = make_decode_fn(model)
    cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    served = []
    for _ in range(new):
        served.append(np.asarray(cur)[:, 0])
        logits, cache = decode(params, cache, cur)
        cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    served = np.stack(served, 1)[:k]
    del params, cache, logits
    gc.collect()
    rows, at = reference.serve_rows(prompts[:k], list(served), P + new - 1)
    w = weights.make(model.abstract_params(), seed)
    ref = np.asarray(reference.logits_fn(cfg, c.REFERENCE)(w, rows, at))
    w = reference.stored_fn(cfg, c.CONTROL)(w)
    ctl = np.asarray(reference.logits_fn(cfg, c.CONTROL)(w, rows, at))
    del w
    return {"program": float(reference.token_gaps(ref, served).max()),
            "control_fp8": float(reference.token_gaps(ref, ctl.argmax(-1)).max())}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    import jax
    from chipbench import cells
    from repro.launch.compile_cache import use_compile_cache
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU")
    use_compile_cache()
    cell = cells.load(args.workload)
    cfg = cells.arch_config(cell.config)
    read = train_readings if cell.traffic["kind"] == "train" else serve_readings
    for seed in args.seeds:
        t0 = time.monotonic()
        out = read(cell, seed, cfg)
        print(json.dumps({"cell": cell.name, "seed": seed, "readings": out,
                          "seconds": time.monotonic() - t0}), flush=True)


if __name__ == "__main__":
    main()
