"""Pallas TPU decode attention (flash-decode): one query token per sequence
against a long KV cache, GQA-aware, with a scalar-prefetched position bound.

Grid = (B, Hq, Skv/BK); the KV dimension is sequential, so the running
(max, denominator, accumulator) live in VMEM scratch — a split-KV
flash-decode. The current position arrives via scalar prefetch (SMEM), so
blocks wholly beyond ``pos`` skip their compute (the loads are still
scheduled by the pipeline, masked compute costs ~nothing on the VPU).

Blocks: q (1,1,1,d) VMEM · k/v (1,1,BK,d) VMEM · acc (8,d) fp32 scratch.
BK=512 default — decode is HBM-bandwidth-bound; larger KV tiles amortize
the grid overhead while staying ≤ 512·160·2·2 B ≈ 320 KiB of VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BK = 512
NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, bk: int, nk: int):
    ki = pl.program_id(2)
    pos = pos_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki * bk <= pos)                      # skip blocks past position
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)       # (1, d)
        k = k_ref[0, 0].astype(jnp.float32)       # (BK, d)
        v = v_ref[0, 0]                           # (BK, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_idx = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where(k_idx <= pos, s, NEG_INF)   # (1, BK)
        # statistics are (1, 1) vectors, stored lane-replicated as whole
        # (1, 128) rows: Mosaic cannot store a scalar to VMEM
        m_prev = m_scr[0:1, 0:1]
        m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_new = l_scr[0:1, 0:1] * corr + p.sum(axis=1, keepdims=True)
        l_scr[0:1, :] = jnp.broadcast_to(l_new, (1, l_scr.shape[1]))
        m_scr[0:1, :] = jnp.broadcast_to(m_cur, (1, m_scr.shape[1]))
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[0:1, :] = acc_scr[0:1, :] * corr + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[0:1, 0:1], 1e-30)
        o_ref[0, 0] = (acc_scr[0:1, :] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention(q, k_cache, v_cache, pos, *, bk: int = DEFAULT_BK,
                     interpret: bool = False):
    """q: (B,Hq,1,d); caches: (B,Hkv,S,d); pos: int32 scalar (last valid
    index). Returns (B,Hq,1,d)."""
    b, hq, _, d = q.shape
    _, hkv, skv, _ = k_cache.shape
    g = hq // hkv
    bk = min(bk, skv)
    assert skv % bk == 0
    nk = skv // bk
    scale = d ** -0.5
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, d), lambda bi, hi, ki, pos: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, ki, pos: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, ki, pos: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d),
                               lambda bi, hi, ki, pos: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((8, 128), jnp.float32),
            pltpu.VMEM((8, 128), jnp.float32),
            pltpu.VMEM((8, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, bk=bk, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, 1, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pos_arr, q, k_cache, v_cache)
