"""Training runtime: jit'd train step (grad-accumulation scan, ZeRO'd AdamW)
plus a fault-tolerant ``Trainer`` that wires the ingestion fabric to the
device mesh: stream → loader → sharded batch → step, with checkpoints that
embed the loader's exactly-once state, failure injection, and auto-resume.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..checkpoint import CheckpointManager, to_device
from ..core.telemetry import span
from ..data.loader import StreamingDataLoader
from ..models import Model, param_spec_tree
from ..models.common import dp_axes, unflatten, param_template
from ..optim import (OptConfig, adamw_init, adamw_update, opt_state_specs,
                     path_tree_of)
from . import tracing


# ---------------------------------------------------------------------------
def make_train_step(model: Model, opt_cfg: OptConfig, *,
                    num_microbatches: int = 1,
                    accum_dtype=jnp.float32,
                    donate: bool = True,
                    grad_reduce_scatter: bool = True):
    """Builds step(params, opt_state, batch, step_idx) -> (params, opt_state,
    metrics). Batch leaves have leading global_batch; with microbatching the
    loss/grads are averaged over a lax.scan of microbatches (activation
    memory = one microbatch).

    grad_reduce_scatter (ZeRO-2): constrain gradients to the optimizer-state
    sharding before the update, so GSPMD emits reduce-scatter instead of
    all-reduce for the cross-DP gradient reduction (≈2× less traffic)."""

    grad_specs = None
    if grad_reduce_scatter and model.mesh is not None:
        ospecs = opt_spec_tree(model, model.mesh)
        grad_specs = ospecs["m"]

    def constrain_grads(grads):
        if grad_specs is None:
            return grads
        return jax.tree.map(
            lambda g, sp: jax.lax.with_sharding_constraint(
                g, NamedSharding(model.mesh, sp)), grads, grad_specs)

    def loss_of(params, batch):
        loss, metrics = model.loss_fn(params, batch)
        return loss, metrics

    def split_mb(batch):
        def rs(x):
            gb = x.shape[0]
            assert gb % num_microbatches == 0, (gb, num_microbatches)
            return x.reshape(num_microbatches, gb // num_microbatches,
                             *x.shape[1:])
        return jax.tree.map(rs, batch)

    def step(params, opt_state, batch, step_idx):
        if num_microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params, batch)
            grads = constrain_grads(grads)
        else:
            mb = split_mb(batch)
            acc0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, accum_dtype), params)
            if grad_specs is not None:
                # ZeRO-2 accumulation: the carry itself is RS-sharded, so
                # each microbatch contributes a reduce-scatter, never a full
                # all-reduce, and the buffer is 1/dp the size
                acc0 = constrain_grads(acc0)

            def body(acc, microbatch):
                (l, m), g = jax.value_and_grad(loss_of, has_aux=True)(
                    params, microbatch)
                g = constrain_grads(g)
                acc = jax.tree.map(lambda a, gg: a + gg.astype(accum_dtype),
                                   acc, g)
                return acc, (l, m)

            acc, (losses, metricses) = jax.lax.scan(body, acc0, mb)
            grads = jax.tree.map(lambda a: a / num_microbatches, acc)
            loss = jnp.mean(losses)
            metrics = jax.tree.map(jnp.mean, metricses)

        paths = path_tree_of(params)
        new_params, new_opt, opt_metrics = adamw_update(
            grads, opt_state, params, step_idx, opt_cfg, path_tree=paths)
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        metrics["loss"] = loss
        return new_params, new_opt, metrics

    donate_argnums = (0, 1) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums)


def shard_batch(batch: dict, mesh: Mesh | None):
    if mesh is None:
        return jax.tree.map(jnp.asarray, batch)
    dp = dp_axes(mesh)
    def put(x):
        spec = P(dp, *([None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))
    return jax.tree.map(put, batch)


def opt_spec_tree(model: Model, mesh: Mesh | None):
    """Sharding spec pytree matching adamw state (ZeRO over 'data')."""
    if mesh is None:
        return None
    from ..models.common import resolved_spec
    from ..optim import zero_spec
    defs = param_template(model.cfg)
    zspecs = unflatten({
        path: zero_spec(d.shape,
                        resolved_spec(d, mesh, model.parallelism),
                        mesh.shape["data"])
        for path, d in defs.items()})
    return {"m": zspecs, "v": zspecs, "master": zspecs, "count": P()}


# ---------------------------------------------------------------------------
class SimulatedFailure(RuntimeError):
    """Injected node failure (tests/benchmarks)."""


@dataclass
class TrainerConfig:
    steps: int = 100
    ckpt_every: int = 25
    ckpt_dir: str = "checkpoints"
    keep_ckpts: int = 3
    log_every: int = 10
    num_microbatches: int = 1
    seed: int = 0
    fail_at_step: int = -1          # failure injection (exercises recovery)


class Trainer:
    """End-to-end driver: owns model, optimizer state, loader, checkpoints.

    Restart contract: ``Trainer.resume()`` (or constructing over an existing
    ckpt_dir) restores params, optimizer, RNG and the loader's stream
    positions — continuing the run produces the SAME batches and, with
    deterministic kernels, the same loss trajectory as an uninterrupted run.
    """

    def __init__(self, model: Model, loader: StreamingDataLoader,
                 opt_cfg: OptConfig, tcfg: TrainerConfig,
                 mesh: Mesh | None = None) -> None:
        self.model = model
        self.loader = loader
        self.opt_cfg = opt_cfg
        self.tcfg = tcfg
        self.mesh = mesh
        self.ckpt = CheckpointManager(tcfg.ckpt_dir, keep=tcfg.keep_ckpts)
        self.step_idx = 0
        self.params = None
        self.opt_state = None
        # the jitted step, kept apart from ``_step_fn``: callers may wrap or
        # replace ``_step_fn``, while ``op_scopes("train_step")`` compiles
        # this one
        self._jitted_step = make_train_step(
            model, opt_cfg, num_microbatches=tcfg.num_microbatches)
        self._step_fn = self._dispatch
        self._registered = False
        self.history: list[dict] = []
        tracing.install()

    # -- lifecycle ------------------------------------------------------------
    def init_state(self) -> None:
        rng = jax.random.PRNGKey(self.tcfg.seed)
        if self.mesh is None:
            self.params = self.model.init(rng)
            self.opt_state = adamw_init(self.params)
            return
        # on a mesh every leaf is created already sharded: no device ever
        # holds the whole parameter or optimizer state
        def shardings(specs):
            return jax.tree.map(lambda sp: NamedSharding(self.mesh, sp), specs,
                                is_leaf=lambda x: isinstance(x, P))
        pspecs = param_spec_tree(self.model.cfg, self.mesh,
                                 self.model.parallelism)
        self.params = jax.jit(self.model.init,
                              out_shardings=shardings(pspecs))(rng)
        self.opt_state = jax.jit(
            adamw_init,
            out_shardings=shardings(opt_spec_tree(self.model, self.mesh)))(
                self.params)

    def resume(self) -> bool:
        """Restore newest intact checkpoint; returns True if resumed."""
        if self.ckpt.latest_step() is None:
            return False
        step, trees, meta = self.ckpt.restore()
        pspecs = (param_spec_tree(self.model.cfg, self.mesh,
                                  self.model.parallelism)
                  if self.mesh else None)
        ospecs = opt_spec_tree(self.model, self.mesh)
        self.params = to_device(trees["params"], pspecs, self.mesh)
        self.opt_state = to_device(trees["opt"], ospecs, self.mesh)
        # counts arrive as np scalars
        self.opt_state["count"] = jnp.asarray(self.opt_state["count"],
                                              jnp.int32)
        self.loader.restore(meta["loader"])
        self.step_idx = step
        return True

    def save(self) -> None:
        self.ckpt.save(self.step_idx,
                       {"params": self.params, "opt": self.opt_state},
                       meta={"loader": self.loader.state(),
                             "step": self.step_idx})

    # -- main loop --------------------------------------------------------------
    def _dispatch(self, params, opt_state, batch, step_idx):
        """Enqueue the jitted step (``train/dispatch``; returns before the
        device finishes)."""
        with span("train/dispatch"):
            return self._jitted_step(params, opt_state, batch, step_idx)

    def run(self, steps: int | None = None) -> dict:
        """Train ``steps`` steps. Each is a ``train/step`` span (trace id:
        the step index, also an xprof step marker) holding the loader's
        spans, ``train/put``, ``train/dispatch``, and on logged steps
        ``train/log_sync``, the only wait on the device."""
        steps = steps if steps is not None else self.tcfg.steps
        if self.params is None and not self.resume():
            self.init_state()
        t0 = time.monotonic()
        trained = 0
        while trained < steps:
            i = self.step_idx
            if i == self.tcfg.fail_at_step:
                raise SimulatedFailure(f"injected at step {i}")
            with jax.profiler.StepTraceAnnotation("train", step_num=i), \
                    span("train/step", trace_id=i):
                batch_np = self.loader.next_batch()
                if batch_np is None:
                    break                               # stream exhausted
                with span("train/put"):
                    batch = shard_batch({"tokens": batch_np}, self.mesh)
                if not self._registered:        # before donation
                    tracing.register("train_step", self._jitted_step,
                                     (self.params, self.opt_state, batch, i))
                    self._registered = True
                self.params, self.opt_state, metrics = self._step_fn(
                    self.params, self.opt_state, batch, i)
                self.step_idx += 1
                trained += 1
                if self.step_idx % self.tcfg.log_every == 0 or trained == steps:
                    with span("train/log_sync"):
                        host = jax.device_get(metrics)
                    row = {k: float(v) for k, v in host.items()}
                    row["step"] = self.step_idx
                    row["elapsed_sec"] = time.monotonic() - t0
                    row["starved_polls"] = self.loader.starved_polls
                    self.history.append(row)
                if (self.tcfg.ckpt_every
                        and self.step_idx % self.tcfg.ckpt_every == 0):
                    with span("train/checkpoint"):
                        self.save()
        with span("train/checkpoint"):
            self.ckpt.wait()
        dt = time.monotonic() - t0
        return {"steps": trained, "wall_sec": dt,
                "final_loss": self.history[-1]["loss"] if self.history else None}
