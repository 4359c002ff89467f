"""The stream->train cell: topic -> ``attach_training_loader`` ->
``Trainer.run``, fed by the generator through the broker.

Set-up builds one ``Trainer`` on weights made from the seed and drives it
through its first steps with ``Trainer.run(steps=1)``: the window's own
call and feed. The window is one ``Trainer.run(steps=N)``, with N taken
from the warm-up step time so that the steps fill ``--seconds``, timed
from ``block_until_ready`` to ``block_until_ready``.

Correctness, once the window has closed: the records consumed are the
generator's, each once and in partition order; every batch trained on is
the packing of those records, computed here; and the first three steps
agree with the plain reference (``families/``) run from the same weights
on the same batches, by loss, by the first gradient and by the change of
the parameters.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from . import harness, stats, workload
from .harness import Check, note

WARM_STEPS = 3


def _leaf_norms(tree, prefix=""):
    import jax.numpy as jnp
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            out.update(_leaf_norms(v, f"{prefix}/{k}" if prefix else k))
        return out
    return {prefix: jnp.sqrt(jnp.sum(jnp.square(tree.astype(jnp.float32))))}


def run(run: harness.Run, children, counter, devs, fault) -> None:
    import jax
    from repro.core.transport import RemoteLogStore
    from repro.data.pipeline import attach_training_loader
    from repro.models import Model
    from repro.optim import OptConfig, adamw_init
    from repro.runtime import Trainer, TrainerConfig
    from . import flops, trace as tr, weights

    t = run.cell.traffic
    B, S, topic, parts = t["batch"], t["seq"], t["topic"], t["partitions"]
    store = RemoteLogStore(children.address, children.run_dir / "device")
    store.create_topic(topic, partitions=parts)
    children.start_generator({"address": children.address, "kind": "train",
                              "root": str(children.run_dir / "generator"),
                              "traffic": t, "seed": run.seed,
                              "seconds": run.seconds})
    children.go(time.monotonic())

    cfg = run.cfg
    opt = OptConfig(**t["opt"])
    _, loader = attach_training_loader(store, topic=topic, batch_size=B, seq_len=S)
    model = Model(cfg)
    trainer = Trainer(model, loader, opt, TrainerConfig(
        steps=1, ckpt_every=0, log_every=t["log_every"],
        ckpt_dir=str(children.run_dir / "ckpt")))
    spans = harness.Spans(run)
    consumed, batches = [], []
    consumer = loader.consumer
    poll = consumer.poll

    def recorded_poll(*a, **k):
        recs = poll(*a, **k)
        consumed.extend((r.partition, r.offset, r.value) for r in recs)
        return recs

    consumer.poll = recorded_poll
    next_batch = loader.next_batch

    def recorded_next_batch(*a, **k):
        b = next_batch(*a, **k)
        if b is not None:
            batches.append(b)
        return b

    loader.next_batch = recorded_next_batch
    spans.wrap(loader, "next_batch", "next_batch")
    trainer.params = weights.make(model.abstract_params(), run.seed)
    trainer.opt_state = jax.jit(adamw_init)(trainer.params)
    if fault is not None:
        fault(trainer)
    spans.wrap(trainer, "_step_fn", "step")

    # set-up: the first steps through the window's own call, recorded for
    # the comparison with the reference
    losses, warm_s = [], []
    norms = jax.jit(_leaf_norms)
    for k in range(WARM_STEPS):
        t0 = time.monotonic()
        trainer.run(steps=1)
        warm_s.append(time.monotonic() - t0)
        losses.append(trainer.history[-1]["loss"])
        if k == 0:
            g1 = {p: float(v) / (1 - opt.beta1)
                  for p, v in norms(trainer.opt_state["m"]).items()}
    p0 = weights.make(model.abstract_params(), run.seed)
    delta = jax.jit(lambda m, p: _leaf_norms(jax.tree.map(
        lambda a, b: a - b.astype(a.dtype), m, p)))
    d3 = {p: float(v) for p, v in delta(trainer.opt_state["master"], p0).items()}
    del p0
    step_s = min(warm_s[1:])
    n_steps = max(1, math.ceil(run.seconds / step_s))

    jax.block_until_ready((trainer.params, trainer.opt_state))
    if run.trace:
        jax.profiler.start_trace(str(children.run_dir / "trace"))
    counter.armed = True
    run.w0 = time.monotonic()
    with spans.span(tr.WINDOW):
        out = trainer.run(steps=n_steps)
        jax.block_until_ready((trainer.params, trainer.opt_state))
    run.w1 = time.monotonic()
    counter.armed = False
    if run.trace:
        jax.profiler.stop_trace()
    run.setup_s = run.w0 - run.t_start
    run.compiles_in_window = counter.count
    run.device = harness.device_info(devs, run.chips)
    trained = out["steps"]
    run.attempted, run.failed = n_steps, n_steps - trained
    run.work = {"steps": trained, "tokens": trained * B * S,
                "step_estimate_s": step_s, "warm_s": warm_s}
    run.window_flops = trained * B * flops.model_flops(cfg, "train", S)
    gen = children.stop_generator()
    late = [s - d for d, s in gen["sent"].values()]
    note(f"generator: {len(late)} documents sent, lateness p50 "
         f"{stats.percentile(late, 50)} s p95 {stats.percentile(late, 95)} s "
         f"max {max(late) if late else float('nan')} s")
    note(f"window: {trained} of {n_steps} steps of {B}x{S} in "
         f"{run.w1 - run.w0} s; compiles in window {run.compiles_in_window}; "
         f"set-up {run.setup_s} s; warm-up steps {warm_s} s")
    if run.trace:
        harness.load_trace(run, str(children.run_dir / "trace"),
                           ("next_batch", "step"))

    # free the program's state before the reference runs
    trainer.params = trainer.opt_state = None
    del trainer
    gc.collect()
    store.close()
    _check(run, consumed, batches, losses, g1, d3, opt)


def packing(run, consumed, n_batches):
    """Batches as the loader should have packed the consumed records, in
    consumption order."""
    t = run.cell.traffic
    return workload.pack((value for _, _, value in consumed), t["batch"],
                         t["seq"], n_batches)


def check_records(run, consumed) -> int:
    """Records consumed that are not the generator's document at their
    place (partition p, offset o holds document o * partitions + p), or
    that repeat or skip an offset."""
    t = run.cell.traffic
    parts, (lo, hi) = t["partitions"], t["doc_words"]
    bad, nxt = 0, {}
    for p, off, value in consumed:
        if off != nxt.get(p, 0):
            bad += 1
        nxt[p] = off + 1
        text = workload.document(run.seed, off * parts + p, lo, hi)
        bad += value != text.encode()
    return bad


def _check(run, consumed, batches, losses, g1, d3, opt) -> None:
    from . import reference
    lim = run.cell.limits["numbers"]
    bad = check_records(run, consumed)
    want = packing(run, consumed, len(batches))
    if want is None:        # batches the consumed records cannot account for
        mism, nums = len(batches), {k: float("nan") for k in lim}
    else:
        mism = int(sum(not np.array_equal(a, b) for a, b in zip(batches, want)))
        ref = reference.train_steps(run.cfg, run.seed, want[:WARM_STEPS], opt)
        nums = reference.compare_train(losses, g1, d3, ref)
        note(f"reference: losses program {losses} reference {ref['losses']}")
    run.checks = [Check("records_wrong", float(bad), 0.0),
                  Check("batches_not_packing", float(mism), 0.0)]
    run.checks += [Check(k, v, lim[k]["limit"]) for k, v in nums.items()]
    for c in run.checks:
        note(f"check {c.name} = {c.value} (limit {c.limit}) "
             f"{'ok' if c.ok else 'FAIL'}")
