"""Pallas TPU kernel for the Mamba-2 SSD chunk scan.

Grid = (B, H, S/Q); the chunk dimension is sequential ('arbitrary'), and the
inter-chunk recurrent state (N, P) lives in fp32 VMEM scratch across chunks
— HBM traffic is one read of (x, B, C), of dt and of its chunk-local
cumulative decay (the wrapper takes that cumsum of dt·A in XLA), and one
write of y per token; the state never leaves VMEM until the final chunk
emits it.

Per-chunk compute (all in VMEM, fp32 accumulation on the MXU):
  scores (Q,Q) = C·Bᵀ  → masked decay weighting → y_intra = M·x
  y_inter (Q,P) = (C ⊙ e^{cum})·state
  state   (N,P) = e^{cum_last}·state + (B ⊙ dt·e^{cum_last-cum})ᵀ·x

Q=128, N=128, P=64..128 keep every matmul MXU-aligned; worst-case VMEM
(Q·N inputs ×3 + Q·Q + state) ≈ 0.4 MB at Q=N=128, P=128.

Caller layout: (B, H, S, ·) — heads-major so one (b, h) grid cell streams a
contiguous sequence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_row_ref, cum_row_ref, dt_col_ref, cum_col_ref,
                b_ref, c_ref, y_ref, state_ref, state_scr, *, nc: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0]                                   # (Q, P)
    dt_r = dt_row_ref[0, 0]                           # (1, Q)
    cum_r = cum_row_ref[0, 0]                         # (1, Q)
    dt_c = dt_col_ref[0, 0]                           # (Q, 1)
    cum_c = cum_col_ref[0, 0]                         # (Q, 1)
    bm = b_ref[0, 0]                                   # (Q, N)
    cm = c_ref[0, 0]                                   # (Q, N)
    q = cum_c.shape[0]
    # the chunk's total decay as a (1, 1) reduction: a slice at offset Q-1
    # leaves a layout Mosaic cannot broadcast back over (Q, ·) tiles
    last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    cum_last = jnp.sum(jnp.where(last, cum_c, 0.0), axis=0, keepdims=True)

    # intra-chunk
    scores = jax.lax.dot_general(cm.astype(jnp.float32),
                                 bm.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q,Q)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    decay = jnp.where(tri, jnp.exp(cum_c - cum_r), 0.0)
    m = scores * decay * dt_r
    y_intra = jax.lax.dot_general(m.astype(x.dtype), x,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    # inter-chunk from carried state
    cin = cm.astype(jnp.float32) * jnp.exp(cum_c)                  # (Q,N)
    y_inter = jax.lax.dot_general(cin, state_scr[...],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update
    dte = dt_c * jnp.exp(cum_last - cum_c)                         # (Q,1)
    binj = bm.astype(jnp.float32) * dte                            # (Q,N)
    bx = jax.lax.dot_general(binj, x.astype(jnp.float32),
                             (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (N,P)
    state_scr[...] = state_scr[...] * jnp.exp(cum_last) + bx

    @pl.when(ci == nc - 1)
    def _emit_state():
        state_ref[0, 0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(x, dt, A, B, C, *, chunk: int = 128, interpret: bool = False):
    """Same contract as ref.ssd_chunked: x (B,S,H,P), dt (B,S,H), A (H,),
    B/C (B,S,H,N) → (y (B,S,H,P), state (B,H,N,P))."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    if s % chunk:
        pad = chunk - s % chunk
        padder = lambda t: jnp.pad(t, [(0, 0), (0, pad)] +
                                   [(0, 0)] * (t.ndim - 2))
        y, state = ssd_pallas(padder(x), padder(dt), A, padder(B), padder(C),
                              chunk=chunk, interpret=interpret)
        return y[:, :s], state
    nc = s // chunk
    # heads-major layout so each (b,h) streams its sequence contiguously
    xh = jnp.moveaxis(x, 2, 1)                        # (B,H,S,P)
    dth = jnp.moveaxis(dt, 2, 1).astype(jnp.float32)  # (B,H,S)
    dah = dth * A.astype(jnp.float32)[None, :, None]
    cum = jnp.cumsum(dah.reshape(b, h, nc, chunk), axis=-1).reshape(b, h, s)
    bh = jnp.moveaxis(B, 2, 1)                        # (B,H,S,N)
    ch = jnp.moveaxis(C, 2, 1)
    # per-step scalars enter both as rows (B,H,1,S) and as columns
    # (B,H,S,1): each block's last two dims are then legal TPU tiles, and
    # the kernel needs no in-VMEM transpose
    row = lambda t: t[:, :, None, :]
    col = lambda t: t[..., None]
    row_spec = pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, 0, ci))
    col_spec = pl.BlockSpec((1, 1, chunk, 1), lambda bi, hi, ci: (bi, hi, ci, 0))

    y, state = pl.pallas_call(
        functools.partial(_ssd_kernel, nc=nc),
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            row_spec, row_spec, col_spec, col_spec,
            pl.BlockSpec((1, 1, chunk, n), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda bi, hi, ci: (bi, hi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, n, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xh, row(dth), row(cum), col(dth), col(cum), bh, ch)
    return jnp.moveaxis(y, 1, 2), state
