"""Mamba-2 (SSD) block: projections + causal depthwise conv + SSD scan +
gated RMSNorm + output projection. Used standalone (mamba2-370m) and as the
SSM branch of the Hymba hybrid block.

Layouts: separate projections per stream (z, x, B, C, dt) so TP sharding is
clean (no uneven slices of one fused projection).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels.ssd import ops as ssd_ops
from .common import ShardCtx, rms_norm


def _causal_conv(x, w, state=None):
    """Depthwise causal conv. x: (B,S,C), w: (K,C). state: (B,K-1,C) carry
    for decode. Returns (y, new_state)."""
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)           # (B,S+K-1,C)
    y = sum(xp[:, i:i + x.shape[1], :] * w[i][None, None, :]
            for i in range(k))
    new_state = xp[:, -(k - 1):, :] if k > 1 else None
    return y, new_state


def _project_streams(h, p, cfg, ctx: ShardCtx):
    dp = ctx.dp or None
    di = cfg.d_inner
    g, n, nh = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_heads
    z = h @ p["in_z"]                                 # (B,S,di)
    xs = h @ p["in_x"]
    if ctx.mesh is not None and nh % ctx.tp == 0:
        z = ctx.cs(z, dp, None, "model")
        xs = ctx.cs(xs, dp, None, "model")
    bs = h @ p["in_B"]                                # (B,S,G*N)
    cs = h @ p["in_C"]
    dt = h @ p["in_dt"] + p["dt_bias"]                # (B,S,H)
    dt = jax.nn.softplus(dt.astype(jnp.float32))
    return z, xs, bs, cs, dt


def _to_heads(xs, bs, cs, cfg):
    """x split into heads (B,S,H,P); B and C into groups (B,S,G,N), at
    group width: the SSD op reads a group once for all of its heads."""
    b, s, _ = xs.shape
    g, n = cfg.ssm_ngroups, cfg.ssm_state
    x = xs.reshape(b, s, cfg.ssm_heads, cfg.ssm_headdim)
    return x, bs.reshape(b, s, g, n), cs.reshape(b, s, g, n)


def _conv_heads(xs, bs, cs, p, cfg, dtype, cache=None):
    """Causal convs of the x, B and C streams (from ``cache``'s carries in
    decode), their SiLU, and the split into heads. Returns the heads and
    the new conv carries."""
    with jax.named_scope("conv"):
        carry = {}
        outs = []
        for key, t in (("conv_x", xs), ("conv_B", bs), ("conv_C", cs)):
            t, carry[key] = _causal_conv(
                t, p[key], None if cache is None else cache[key])
            outs.append(jax.nn.silu(t.astype(jnp.float32)).astype(dtype))
        return (*_to_heads(*outs, cfg), carry)


def _ssd_skip(x, dt, bm, cm, p, cfg, ctx: ShardCtx, state=None):
    """The SSD over the sequence (or, from ``state``, one decode step) plus
    the D skip. Returns (y, final state)."""
    with jax.named_scope("ssd"):
        A = -jnp.exp(p["A_log"].astype(jnp.float32))
        if state is None:
            y, state = ssd_ops.ssd(x, dt, A, bm, cm, chunk=cfg.ssm_chunk,
                                   mesh=ctx.mesh)
        else:
            y, state = ssd_ops.ssd_decode_step(
                state, x[:, 0], dt[:, 0], A, bm[:, 0], cm[:, 0])
            y = y[:, None]
        return y + x * p["D"].astype(x.dtype)[None, None, :, None], state


def _gate_out(y, z, p, cfg, h):
    """Gated RMSNorm, then the output projection."""
    with jax.named_scope("gate_norm"):
        y = y.reshape(h.shape[0], h.shape[1], cfg.d_inner)
        y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                     p["gate_norm"], cfg.norm_eps)
    with jax.named_scope("mixer_proj"):
        return y @ p["out_proj"]


def _streams(h, p, cfg, ctx):
    with jax.named_scope("mixer_proj"):
        return _project_streams(h, p, cfg, ctx)


def mamba_forward(h, p, cfg, ctx: ShardCtx):
    """Training/prefill path over a full sequence. h: (B,S,d)."""
    z, xs, bs, cs, dt = _streams(h, p, cfg, ctx)
    x, bm, cm, _ = _conv_heads(xs, bs, cs, p, cfg, h.dtype)
    y, _ = _ssd_skip(x, dt, bm, cm, p, cfg, ctx)
    return _gate_out(y, z, p, cfg, h)


def mamba_prefill(h, p, cfg, ctx: ShardCtx):
    """Like forward but also returns the recurrent cache for decode."""
    z, xs, bs, cs, dt = _streams(h, p, cfg, ctx)
    x, bm, cm, carry = _conv_heads(xs, bs, cs, p, cfg, h.dtype)
    y, state = _ssd_skip(x, dt, bm, cm, p, cfg, ctx)
    cache = {"ssm": state, **carry}                # ssm: (B,H,N,P) fp32
    return _gate_out(y, z, p, cfg, h), cache


def mamba_decode(h, p, cfg, ctx: ShardCtx, cache):
    """One-token step. h: (B,1,d). cache: {'ssm','conv_x','conv_B','conv_C'}."""
    z, xs, bs, cs, dt = _streams(h, p, cfg, ctx)
    x, bm, cm, carry = _conv_heads(xs, bs, cs, p, cfg, h.dtype, cache)
    y, state = _ssd_skip(x, dt, bm, cm, p, cfg, ctx, cache["ssm"])
    return _gate_out(y, z, p, cfg, h), {"ssm": state, **carry}


def mamba_cache_shape(cfg, batch: int) -> dict:
    """Per-layer cache shapes (fp32 state, bf16 conv carries)."""
    k = cfg.ssm_conv
    g, n = cfg.ssm_ngroups, cfg.ssm_state
    return {
        "ssm": ((batch, cfg.ssm_heads, n, cfg.ssm_headdim), jnp.float32),
        "conv_x": ((batch, k - 1, cfg.d_inner), cfg.dtype),
        "conv_B": ((batch, k - 1, g * n), cfg.dtype),
        "conv_C": ((batch, k - 1, g * n), cfg.dtype),
    }
