"""Public SSD op. B and C come at group width, (B, S, G, N).

The path follows what the call can observe:
  kernel  — the fused Pallas kernels (kernel.py, forward and backward)
            when the platform is a TPU and the operands live on one
            device (no mesh);
  chunked — ref.ssd_chunked in pure jnp on any other platform, and under
            a mesh: a custom call there would make the compiler gather
            the heads that the mesh shards on 'model'.
Each trace counts its path in the process tracer's ``ssd_path{path=...}``,
so a scrape says which path a program was compiled with.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.telemetry import tracer
from . import ref
from .kernel import ssd_pallas


def _per_head(t, heads: int):
    """(..., G, N) at group width → (..., H, N), head h reading group
    h // (H/G)."""
    return jnp.repeat(t, heads // t.shape[-2], axis=-2)


def ssd(x, dt, A, B, C, *, chunk: int = 64, mesh=None):
    """y (B,S,H,P), final state (B,H,N,P) fp32 of the SSD over x (B,S,H,P),
    dt (B,S,H), A (H,) and B/C (B,S,G,N); ``mesh`` is the mesh the
    operands are sharded on, None for one device."""
    kernel = mesh is None and jax.default_backend() == "tpu"
    tracer().count("ssd_path", path="kernel" if kernel else "chunked")
    if kernel:
        return ssd_pallas(x, dt, A, B, C, chunk=chunk)
    h = x.shape[2]
    return ref.ssd_chunked(x, dt, A, _per_head(B, h), _per_head(C, h),
                           chunk=chunk)


def ssd_decode_step(state, x_t, dt_t, A, B_t, C_t):
    """One recurrent step; B_t/C_t (B,G,N) at group width."""
    h = x_t.shape[1]
    return ref.ssd_decode_step(state, x_t, dt_t, A, _per_head(B_t, h),
                               _per_head(C_t, h))
