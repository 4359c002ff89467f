"""Random weights made on the device from ``--seed``, in one jitted call,
in the type they are served in.

The tree's structure and shapes are the program's parameter layout; the
values are the benchmark's own: each leaf is drawn from a key folded from
the seed and the leaf's path, so the reference can make the same weights
again without taking anything the program made.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

ONES = ("norm", "D", "beta_attn", "beta_ssm")


def seed_key(seed: int):
    """A JAX key from any non-negative seed, including ones past 2**32."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def _leaf(key, path: str, shape, dtype):
    last = path.split("/")[-1]
    if last == "A_log":                 # decay rates A = -exp(A_log) in [-16, -1]
        u = jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(dtype)
    if last == "dt_bias":               # softplus(dt_bias) in [1e-3, 0.1]
        u = jax.random.uniform(key, shape, jnp.float32, 1e-3, 0.1)
        return jnp.log(jnp.expm1(u)).astype(dtype)
    if any(last.endswith(s) for s in ONES):
        return jnp.ones(shape, dtype)
    if last == "embed":
        fan_in = shape[-1]
    elif last in ("meta_k", "meta_v"):
        fan_in = shape[-1]
    else:
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _paths(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _paths(tree[k], f"{prefix}/{k}" if prefix else k)
    else:
        yield prefix, tree


def _unflatten(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for p in head:
            node = node.setdefault(p, {})
        node[last] = v
    return out


def make(abstract: dict, seed: int):
    """Weights for the tree of ``jax.ShapeDtypeStruct`` ``abstract``, in
    each leaf's own dtype; one compiled program."""
    leaves = list(_paths(abstract))

    def build(key):
        return _unflatten({
            p: _leaf(jax.random.fold_in(key, zlib.crc32(p.encode())), p,
                     s.shape, s.dtype)
            for p, s in leaves})

    return jax.jit(build)(seed_key(seed))
