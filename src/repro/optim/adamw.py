"""AdamW with fp32 master weights and m/v moments, built for ZeRO-1 sharding:
optimizer state lives in its own pytree whose sharding adds the 'data' axis
on the largest divisible dimension of each tensor (see ``zero_spec``).

Params stay bf16; the update path is fp32 end-to-end
(grad -> m/v -> master -> cast-down), so repeated restarts are bit-stable.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class OptConfig:
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0


def warmup_cosine(cfg: OptConfig, step):
    step = step.astype(jnp.float32) if hasattr(step, "astype") else float(step)
    warm = jnp.minimum(1.0, (step + 1) / max(1, cfg.warmup_steps))
    prog = jnp.clip((step - cfg.warmup_steps)
                    / max(1, cfg.total_steps - cfg.warmup_steps), 0.0, 1.0)
    cos = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return cfg.peak_lr * warm * cos


def adamw_init(params):
    f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)
    zeros = lambda t: jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), t)
    return {"m": zeros(params), "v": zeros(params), "master": f32(params),
            "count": jnp.zeros((), jnp.int32)}


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def clip_by_global_norm(grads, max_norm):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale), grads), norm


def _decay_mask(path: str) -> float:
    """No weight decay on norms/scalars (standard)."""
    last = path.split("/")[-1]
    if "norm" in last or last in ("A_log", "D", "dt_bias", "beta_attn",
                                  "beta_ssm"):
        return 0.0
    return 1.0


@jax.named_scope("optimizer")
def adamw_update(grads, state, params, step, cfg: OptConfig,
                 path_tree=None):
    """Returns (new_params (model dtype), new_state). grads may be any float
    dtype (bf16 accumulators upcast here)."""
    grads, gnorm = clip_by_global_norm(grads, cfg.clip_norm)
    lr = warmup_cosine(cfg, step)
    b1, b2 = cfg.beta1, cfg.beta2
    count = state["count"] + 1
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)

    def upd(g, m, v, master, wd_scale):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        mhat = m / c1
        vhat = v / c2
        step_vec = mhat / (jnp.sqrt(vhat) + cfg.eps)
        master = master - lr * (step_vec + cfg.weight_decay * wd_scale * master)
        return m, v, master

    if path_tree is None:
        wd = jax.tree.map(lambda _: 1.0, params)
    else:
        wd = jax.tree.map(_decay_mask, path_tree)
    out = jax.tree.map(upd, grads, state["m"], state["v"], state["master"], wd)
    m = jax.tree.map(lambda t: t[0], out, is_leaf=lambda t: isinstance(t, tuple))
    v = jax.tree.map(lambda t: t[1], out, is_leaf=lambda t: isinstance(t, tuple))
    master = jax.tree.map(lambda t: t[2], out, is_leaf=lambda t: isinstance(t, tuple))
    new_params = jax.tree.map(lambda mstr, p: mstr.astype(p.dtype),
                              master, params)
    new_state = {"m": m, "v": v, "master": master, "count": count}
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}


def path_tree_of(params) -> dict:
    """Mirror pytree whose leaves are their own 'a/b/c' paths."""
    def walk(node, prefix):
        if isinstance(node, dict):
            return {k: walk(v, f"{prefix}/{k}" if prefix else k)
                    for k, v in node.items()}
        return prefix
    return walk(params, "")


def zero_spec(shape: tuple[int, ...], spec: P, data_size: int,
              min_dim: int = 128) -> P:
    """ZeRO-1: add 'data' to the largest unsharded, divisible axis."""
    parts = list(spec) + [None] * (len(shape) - len(spec))
    best, best_dim = -1, 0
    for i, (dim, sh) in enumerate(zip(shape, parts)):
        if sh is None and dim % data_size == 0 and dim >= max(min_dim, data_size):
            if dim > best_dim:
                best, best_dim = i, dim
    if best >= 0:
        parts[best] = "data"
    return P(*parts)


def opt_state_specs(param_defs: dict, data_size: int):
    """param_defs: flat path -> ParamDef. Returns flat path -> P for one
    fp32 state tensor (same for m, v, master)."""
    return {path: zero_spec(d.shape, d.spec, data_size)
            for path, d in param_defs.items()}
