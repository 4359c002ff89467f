"""Smoke run of the stream→train and stream→serve path on TPU, at full width.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # the sharded training phase alone

One chip, in one process and in this order:
  1. device check: exit non-zero unless JAX's first device is a TPU;
  2. ingest: the news case study through the two-worker fabric, with no
     article lost;
  3. train: mamba2-370m from the landed topic, 8 x 2048 tokens a step, 5
     steps, a checkpoint at the last one, then a resume and one more step;
  4. serve: hymba-1.5b answers 32 requests of 1024-token prompts, and one
     served batch's prefill + decode logits are checked against the
     model's full forward over the same tokens.

``--chips 4`` trains hymba-1.5b on a (2, 2) data x model mesh for 2 steps
and checks step 0's loss and gradient norm against the unsharded loss on
one chip, with the same parameters and batch.

Any failure exits non-zero. The last line of stdout is one JSON object
naming the device. Scratch state (a 6 GB checkpoint) lives in
``.smoke_run/`` of the checkout and is deleted at exit.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import shutil
import sys
from pathlib import Path

# Only the standard library at module level: the fabric's workers are
# spawned, and re-import this module before they run.
ROOT = Path(__file__).resolve().parent
RUN_DIR = ROOT / ".smoke_run"

TRAIN_ARCH, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = "mamba2-370m", 8, 2048, 5
SERVE_ARCH, SERVE_REQUESTS, SERVE_BATCH = "hymba-1.5b", 32, 8
PROMPT_LEN, MAX_NEW = 1024, 32
# batch 4: the one-chip reference's forward and backward over 8 x 2048
# tokens would need 16.9 GB (compile-only memory_analysis for v5e)
MESH_ARCH, MESH_SHAPE, MESH_BATCH, MESH_SEQ, MESH_STEPS = \
    "hymba-1.5b", (2, 2), 4, 2048, 2

# Served logits against the full forward: the largest absolute difference
# over a batch's 32 positions, as a share of the largest reference logit.
# Both paths run in bf16 (8-bit mantissa, 2^-8 per rounding) through 32
# layers, and they sum in different orders: blockwise prefill attention
# and a chunked SSD scan on one side, one-token attention over the cache
# and the recurrent SSM step on the other.
SERVE_TOL = 5e-2
# Sharded step 0 against the one-chip loss on the same parameters and
# batch: relative differences. The loss is an f32 mean over 8k tokens;
# the gradient norm sums bf16 gradients that the mesh reduces in another
# order (reduce-scatter over 'data', sequence-sharded attention).
LOSS_RTOL, GNORM_RTOL = 1e-2, 5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def device_check(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"no TPU: JAX's first device is {devs[0].platform}")
    if len(devs) < chips:
        sys.exit(f"{chips} chips asked for, {len(devs)} found")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache
    log(f"jax {jax.__version__} device_kind={devs[0].device_kind} "
        f"devices={len(devs)} compile_cache={use_compile_cache()}")
    return devs


def ingest(root: Path):
    """The case study over two worker processes; returns the store."""
    from repro.core.flowfile import FlowFile
    from repro.data.pipeline import (build_news_pipeline,
                                     expected_fabric_doc_ids,
                                     landed_doc_ids_by_shard)
    fabric, store = build_news_pipeline(root, n_rss=2000, n_firehose=2000,
                                        n_ws=200, partitions=8, workers=2)
    fabric.start()
    fabric.wait(timeout=600.0)
    exp = expected_fabric_doc_ids(list(fabric.shards.values()))
    ids, counts = landed_doc_ids_by_shard(store)
    lost = sum(len(exp[g] - ids.get(g, set())) for g in exp)
    dupes = sum(counts[g] - len(ids[g]) for g in counts)
    landed = sum(store.end_offsets("articles"))
    tokens = sum(len(FlowFile.from_record(r.key, r.value)
                     .attributes.get("text", "").encode()) + 1
                 for p in range(store.num_partitions("articles"))
                 for r in store.iter_records("articles", p))
    need = (TRAIN_STEPS + 1) * TRAIN_BATCH * (TRAIN_SEQ + 1)
    log(f"ingest: landed={landed} lost={lost} duplicates={dupes} "
        f"tokens={tokens} (training needs {need})")
    assert lost == 0, f"{lost} articles lost"
    assert tokens >= need, f"{tokens} tokens landed, {need} needed"
    return store


def release(trainer) -> None:
    """Drop a trainer's device state so the next phase has the chip."""
    trainer.params = trainer.opt_state = None
    gc.collect()


def check_rows(rows, n: int, what: str) -> None:
    assert len(rows) == n, f"{what}: {len(rows)} logged steps, {n} expected"
    for r in rows:
        assert math.isfinite(r["loss"]) and math.isfinite(r["grad_norm"]), \
            f"{what}: non-finite step {r}"


def train_phase(root: Path, store) -> None:
    from repro import configs
    from repro.launch.train import train
    cfg = configs.get(TRAIN_ARCH)
    tr = train(cfg, root, log=store, steps=TRAIN_STEPS, batch=TRAIN_BATCH,
               seq=TRAIN_SEQ, ckpt_every=TRAIN_STEPS, log_every=1)
    rows = tr.history
    check_rows(rows, TRAIN_STEPS, "train")
    assert tr.ckpt.steps() == [TRAIN_STEPS], tr.ckpt.steps()
    steady = ((rows[-1]["elapsed_sec"] - rows[0]["elapsed_sec"])
              / (TRAIN_STEPS - 1))
    log(f"train: {TRAIN_ARCH} {TRAIN_STEPS} steps of {TRAIN_BATCH}x"
        f"{TRAIN_SEQ}; first step incl. compile {rows[0]['elapsed_sec']} s, "
        f"steady {steady} s/step (not a benchmark); losses "
        f"{[r['loss'] for r in rows]}; grad norms "
        f"{[r['grad_norm'] for r in rows]}; checkpoint at step "
        f"{tr.ckpt.steps()[0]}")
    release(tr)

    tr = train(cfg, root, log=store, steps=1, batch=TRAIN_BATCH,
               seq=TRAIN_SEQ, ckpt_every=0, resume=True, log_every=1)
    check_rows(tr.history, 1, "resume")
    assert tr.step_idx == TRAIN_STEPS + 1, tr.step_idx
    log(f"resume: step {tr.step_idx} loss {tr.history[0]['loss']} "
        f"grad_norm {tr.history[0]['grad_norm']}")
    release(tr)
    shutil.rmtree(root / "ckpt")


def serve_phase(root: Path) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import configs
    from repro.core import PartitionedLog
    from repro.launch.serve import serve
    cfg = configs.get(SERVE_ARCH)
    store = PartitionedLog(root / "log")
    srv = serve(cfg, log=store, requests=SERVE_REQUESTS, batch=SERVE_BATCH,
                prompt_len=PROMPT_LEN, max_new=MAX_NEW)
    done = {}
    for p in range(store.num_partitions("completions")):
        for r in store.iter_records("completions", p):
            doc = json.loads(r.value)
            done[doc["id"]] = doc["completion_ids"]
    log(f"serve: {SERVE_ARCH} {len(done)} completions landed for "
        f"{SERVE_REQUESTS} requests ({SERVE_BATCH} a batch, prompts of "
        f"{PROMPT_LEN} tokens, {MAX_NEW} new tokens each)")
    assert sorted(done) == sorted(str(i) for i in range(SERVE_REQUESTS))

    # one served batch, teacher-forced: prefill the prompts, then decode
    # the tokens the server produced, through the server's own programs
    reqs = sorted((r for p in range(store.num_partitions("requests"))
                   for r in store.iter_records("requests", p)),
                  key=lambda r: int(r.key))[:SERVE_BATCH]
    prompts, ids = srv.batch_prompts(reqs)
    gen = np.asarray([done[i] for i in ids], np.int32)
    assert gen.shape == (SERVE_BATCH, MAX_NEW), gen.shape
    logits, cache = srv._prefill(srv.params, {"tokens": jnp.asarray(prompts)})
    got = [logits]
    for t in range(MAX_NEW - 1):
        logits, cache = srv._decode(srv.params, cache,
                                    jnp.asarray(gen[:, t:t + 1]))
        got.append(logits)
    got = np.asarray(jnp.stack(got, axis=1), np.float32)
    del cache
    full = jnp.asarray(np.concatenate([prompts, gen[:, :-1]], axis=1))
    ref, _ = jax.jit(lambda p, b: srv.model.forward(p, b, mode="prefill"))(
        srv.params, {"tokens": full})
    ref = np.asarray(ref[:, PROMPT_LEN - 1:], np.float32)
    scale = float(np.abs(ref).max())
    err = float(np.abs(got - ref).max())
    top1 = float((got.argmax(-1) == ref.argmax(-1)).mean())
    log(f"serve check: prefill+decode vs forward over {full.shape[1]} "
        f"tokens: max|diff|={err} max|ref|={scale} rel={err / scale} "
        f"(tol {SERVE_TOL}) top1_agree={top1}")
    assert np.isfinite(got).all(), "non-finite served logits"
    assert err <= SERVE_TOL * scale, f"rel err {err / scale} > {SERVE_TOL}"
    store.close()


def mesh_phase(root: Path, devs) -> None:
    """hymba-1.5b on a (2, 2) mesh vs one chip, same params and batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import configs
    from repro.data.pipeline import attach_training_loader
    from repro.launch.mesh import make_mesh
    from repro.launch.train import corpus_log
    from repro.models import Model
    from repro.optim import OptConfig
    from repro.optim.adamw import global_norm
    from repro.runtime import Trainer, TrainerConfig
    cfg = configs.get(MESH_ARCH)
    mesh = make_mesh(MESH_SHAPE, ("data", "model"))
    store = corpus_log(root, docs=2000)
    _, loader = attach_training_loader(store, batch_size=MESH_BATCH,
                                       seq_len=MESH_SEQ)
    tr = Trainer(Model(cfg, mesh), loader, OptConfig(total_steps=MESH_STEPS),
                 TrainerConfig(steps=MESH_STEPS, ckpt_every=0, log_every=1,
                               ckpt_dir=str(root / "ckpt")), mesh=mesh)
    tr.init_state()
    host_params = jax.device_get(tr.params)
    state = loader.state()
    batch = loader.next_batch()                 # the batch step 0 will see
    loader.restore(state)
    tr.run()
    check_rows(tr.history, MESH_STEPS, "mesh train")
    rows = tr.history
    log(f"mesh train: {MESH_ARCH} on {MESH_SHAPE} data x model, "
        f"{MESH_STEPS} steps of {MESH_BATCH}x{MESH_SEQ}; losses "
        f"{[r['loss'] for r in rows]}; grad norms "
        f"{[r['grad_norm'] for r in rows]}; first step incl. compile "
        f"{rows[0]['elapsed_sec']} s")
    release(tr)

    one = jax.sharding.SingleDeviceSharding(devs[0])
    params = jax.device_put(host_params, one)
    del host_params
    model = Model(cfg)

    @jax.jit
    def loss_and_norm(p, b):
        (loss, _), grads = jax.value_and_grad(model.loss_fn, has_aux=True)(
            p, b)
        return loss, global_norm(grads)

    loss, gnorm = loss_and_norm(
        params, {"tokens": jax.device_put(jnp.asarray(batch), one)})
    loss, gnorm = float(loss), float(gnorm)
    dl = abs(rows[0]["loss"] - loss) / abs(loss)
    dg = abs(rows[0]["grad_norm"] - gnorm) / gnorm
    log(f"mesh check: one-chip loss {loss} grad_norm {gnorm}; sharded step 0 "
        f"loss {rows[0]['loss']} grad_norm {rows[0]['grad_norm']}; rel diff "
        f"loss {dl} (tol {LOSS_RTOL}) grad_norm {dg} (tol {GNORM_RTOL})")
    assert dl <= LOSS_RTOL and dg <= GNORM_RTOL
    store.close()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded training phase")
    args = ap.parse_args()
    devs = device_check(args.chips)
    shutil.rmtree(RUN_DIR, ignore_errors=True)
    try:
        if args.chips == 4:
            mesh_phase(RUN_DIR / "mesh", devs)
        else:
            store = ingest(RUN_DIR / "ingest")
            train_phase(RUN_DIR / "train", store)
            store.close()
            serve_phase(RUN_DIR / "serve")
    finally:
        shutil.rmtree(RUN_DIR, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
