"""Pallas TPU kernels for the Mamba-2 SSD chunk scan, forward and backward,
joined by a custom VJP.

Grid = (B, G, S/Q, Hg/hb): per batch row and group, the chunks of Q steps
in order, and within a chunk the group's Hg heads in blocks of hb; the
chunk and head-block axes are sequential ('arbitrary'). The group's C·Bᵀ
(Q, Q) is computed once per chunk, at its first head block, and kept in
VMEM for the others. Nothing of shape (b, s, h, n) or (b, c, h, i, j)
reaches HBM: the (Q, Q) blocks live in VMEM only.

Forward, per head (fp32 accumulation on the MXU, decays in fp32):
  L (Q,Q)     = e^{cum_i - cum_j} for i ≥ j, else 0
  y (Q,P)     = (C·Bᵀ ⊙ L ⊙ dt_j)·x + (C ⊙ e^{cum})·S
  S' (N,P)    = e^{cum_last}·S + (B ⊙ dt·e^{cum_last-cum})ᵀ·x
where ``cum`` is the chunk-local cumulative sum of dt·A, taken in XLA by
the wrapper, so that XLA differentiates dt and A through it and the
backward kernel returns only d(cum). The carried state S of every head
of the group lives in fp32 VMEM scratch across chunks. For the backward,
the forward also writes each chunk's starting states (fp32) to HBM: they
are read once there, cheaper than a second pass to recompute them.

Backward: the same grid with the chunks in reverse, carrying d(S) per head
in fp32 VMEM scratch from the final state's cotangent. Per chunk it
recomputes C·Bᵀ and L in VMEM and gives dx, the direct d(dt), d(cum), and
dB and dC summed over the group's heads in VMEM before they are written.

Precision, as the chunked jnp path (ref.ssd_chunked) has it on the TPU,
where an fp32 dot at DEFAULT precision is one bf16 pass: the (Q, Q)
operands (the decay-weighted scores and their cotangents) go to the MXU
in the inputs' dtype, the (Q, N)·(N, P) state products in fp32 at DEFAULT
precision, all with fp32 accumulation; every exponent, mask, decay and
state stays fp32.

Layouts: x, y and their cotangents as the model holds them, (B, S, H·P),
a step's block being its hb heads' lanes, worked in tiles of 128 lanes:
heads narrower than that share a tile (two heads of 64), each head's
results selected by a lane mask, so that no head is ever shifted across
lanes. B, C (B, G, S, N); per-step scalars dt and cum as rows
(B, H/hb, hb, S), which the body transposes once per step in VMEM for the
columns (Q, 1) that scale rows of (Q, ·) tiles; starting states
(B, S/Q, N, H·P); the carried states as tiles (N, 128).
"""
from __future__ import annotations

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_MAX_HEAD_BLOCK = 16


def _dot(a, b, contract):
    """``a``·``b`` over dims ``contract`` = (a's, b's), fp32 accumulation."""
    return jax.lax.dot_general(a, b, ((contract[0], contract[1]), ((), ())),
                               preferred_element_type=F32)


def _chunk_frame(q):
    """The causal mask (Q, Q) and a (1, Q) row that is true at lane Q-1."""
    tri = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    last = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    return tri, last


def _tiles(dt_ref, cum_ref, last, t, body):
    """``body(r, heads)`` for each tile r of t heads (W = t·P lanes) of the
    step's block, unrolled; ``heads`` lists the tile's heads as (kk, its
    place in the tile; scalars: its dt and cum as rows (1, Q) and as
    columns (Q, 1), and the chunk's total decay exponent (1, 1)). Heads
    narrower than 128 lanes share a tile, so that every load and store of
    x, y and the states is a whole aligned tile: a head's results are
    selected by its lane mask, never shifted."""
    dt_rows, cum_rows = dt_ref[0, 0], cum_ref[0, 0]     # (hb, Q)
    dt_cols, cum_cols = dt_rows.T, cum_rows.T           # (Q, hb)
    hb = dt_rows.shape[0]
    for r in range(hb // t):
        heads = []
        for kk in range(t):
            k = r * t + kk
            cum_r = cum_rows[k:k + 1]
            # a (1, 1) reduction: a slice at lane Q-1 leaves a layout
            # Mosaic cannot broadcast back over (Q, ·) tiles
            cum_last = jnp.sum(jnp.where(last, cum_r, 0.0), axis=1,
                               keepdims=True)
            heads.append((kk, (dt_rows[k:k + 1], cum_r, dt_cols[:, k:k + 1],
                               cum_cols[:, k:k + 1], cum_last)))
        body(r, heads)


def _lane_mask(w, p, kk):
    """(1, W): true on the lanes of the tile's head kk (P lanes each)."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, w), 1) // p == kk


def _scores(g_scr, b_ref, c_ref, mx):
    """The group's C·Bᵀ (Q, Q) for this chunk, into ``g_scr``."""
    g_scr[...] = _dot(c_ref[0, 0].astype(mx), b_ref[0, 0].astype(mx),
                      ((1,), (1,)))


def _fwd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, y_ref, state_ref,
                *rest, t: int, p: int):
    *starts_ref, s_scr, g_scr = rest           # starts only when saved
    ci, j = pl.program_id(2), pl.program_id(3)
    mx = x_ref.dtype
    tri, last = _chunk_frame(b_ref.shape[2])
    w = t * p
    nt = dt_ref.shape[2] // t                  # tiles a step

    @pl.when(ci == 0)
    def _init():
        s_scr[pl.ds(j * nt, nt)] = jnp.zeros((nt,) + s_scr.shape[1:], F32)

    pl.when(j == 0)(lambda: _scores(g_scr, b_ref, c_ref, mx))

    def tile(r, heads):
        lanes = slice(r * w, (r + 1) * w)
        x = x_ref[0, :, lanes]                             # (Q, W)
        s = s_scr[j * nt + r]                              # (N, W) fp32
        if starts_ref:
            starts_ref[0][0, 0, :, lanes] = s
        y = upd = decay = None
        for kk, (dt_r, cum_r, dt_c, cum_c, cum_last) in heads:
            m = (g_scr[...] * jnp.where(tri, jnp.exp(cum_c - cum_r), 0.0)
                 * dt_r)
            cin = c_ref[0, 0].astype(F32) * jnp.exp(cum_c)     # (Q, N)
            yk = (_dot(m.astype(mx), x, ((1,), (0,)))
                  + _dot(cin, s, ((1,), (0,))))
            bw = b_ref[0, 0].astype(F32) * (dt_c * jnp.exp(cum_last - cum_c))
            uk = _dot(bw, x.astype(F32), ((0,), (0,)))         # (N, W)
            dk = jnp.exp(cum_last)
            if y is None:
                y, upd, decay = yk, uk, dk
            else:
                mask = _lane_mask(w, p, kk)
                y = jnp.where(mask, yk, y)
                upd = jnp.where(mask, uk, upd)
                decay = jnp.where(mask, dk, decay)
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        s = s * decay + upd
        s_scr[j * nt + r] = s
        state_ref[0, j * nt + r] = s

    _tiles(dt_ref, cum_ref, last, t, tile)


def _bwd_kernel(x_ref, dy_ref, dt_ref, cum_ref, b_ref, c_ref, starts_ref,
                dsf_ref, dx_ref, ddt_ref, dcum_ref, db_ref, dc_ref,
                ds_scr, g_scr, dg_scr, db_scr, dc_scr, ddtc_scr, dcumc_scr,
                *, t: int, p: int, nhb: int):
    ci, j = pl.program_id(2), pl.program_id(3)
    mx = x_ref.dtype
    tri, last = _chunk_frame(b_ref.shape[2])
    w = t * p
    nt = dt_ref.shape[2] // t

    @pl.when(ci == 0)
    def _init():
        ds_scr[pl.ds(j * nt, nt)] = dsf_ref[0, pl.ds(j * nt, nt)]

    @pl.when(j == 0)
    def _group_init():
        _scores(g_scr, b_ref, c_ref, mx)
        dg_scr[...] = jnp.zeros_like(dg_scr)   # Σ over heads of d(C·Bᵀ)
        db_scr[...] = jnp.zeros_like(db_scr)
        dc_scr[...] = jnp.zeros_like(dc_scr)

    def tile(r, heads):
        lanes = slice(r * w, (r + 1) * w)
        x, dy = x_ref[0, :, lanes], dy_ref[0, :, lanes]    # (Q, W)
        bf, cf = b_ref[0, 0].astype(F32), c_ref[0, 0].astype(F32)
        ds = ds_scr[j * nt + r]        # d(state at the chunk's end) (N, W)
        s0 = starts_ref[0, 0, :, lanes]    # state at the chunk's start
        dx = upd = decay = None
        for kk, (dt_r, cum_r, dt_c, cum_c, cum_last) in heads:
            k = r * t + kk
            mask = _lane_mask(w, p, kk)
            # the head's own lanes of dy and x: a contraction over the
            # tile's lanes is then over this head's alone
            dyk = jnp.where(mask, dy, 0) if t > 1 else dy
            xk = jnp.where(mask, x, 0) if t > 1 else x
            lmat = jnp.where(tri, jnp.exp(cum_c - cum_r), 0.0)
            gl = g_scr[...] * lmat
            ecum = jnp.exp(cum_c)                              # (Q, 1)
            e_end = jnp.exp(cum_last - cum_c)                  # (Q, 1)
            wt = dt_c * e_end
            dm = _dot(dyk, x, ((1,), (1,)))                    # (Q, Q)
            dxk = (_dot((gl * dt_r).astype(mx), dy, ((0,), (0,)))
                   + _dot(bf * wt, ds, ((1,), (0,))))          # (Q, W)
            dcin = _dot(dyk.astype(F32), s0, ((1,), (1,)))     # (Q, N)
            dbin = _dot(xk.astype(F32), ds, ((1,), (1,)))      # (Q, N)
            dc_scr[...] += dcin * ecum
            db_scr[...] += dbin * wt
            uk = _dot(cf * ecum, dy.astype(F32), ((0,), (0,)))  # (N, W)
            dk = jnp.exp(cum_last)
            if dx is None:
                dx, upd, decay = dxk, uk, dk
            else:
                dx = jnp.where(mask, dxk, dx)
                upd = jnp.where(mask, uk, upd)
                decay = jnp.where(mask, dk, decay)
            dg_scr[...] += dm * lmat * dt_r
            u = dm * gl                        # d(M) ⊙ M = u ⊙ dt_j
            ddt_intra = jnp.sum(u, axis=0, keepdims=True)      # (1, Q)
            dw = jnp.sum(dbin * bf, axis=1, keepdims=True)     # (Q, 1)
            dwx = dw * wt
            sds = ds * s0 if t == 1 else jnp.where(mask, ds * s0, 0.0)
            s_dot = jnp.sum(jnp.sum(sds, axis=1, keepdims=True), axis=0,
                            keepdims=True)
            dcum_last = (jnp.sum(dwx, axis=0, keepdims=True)
                         + jnp.exp(cum_last) * s_dot)          # (1, 1)
            # the rows' terms go out now, the columns' (Q, 1) terms into
            # the block's (Q, hb) scratch, added transposed after it
            ddt_ref[0, 0, k:k + 1, :] = ddt_intra
            dcum_ref[0, 0, k:k + 1, :] = (
                jnp.where(last, dcum_last, 0.0) - ddt_intra * dt_r)
            ddtc_scr[:, k:k + 1] = dw * e_end
            dcumc_scr[:, k:k + 1] = (
                jnp.sum(u * dt_r, axis=1, keepdims=True)
                + jnp.sum(dcin * cf, axis=1, keepdims=True) * ecum - dwx)
        dx_ref[0, :, lanes] = dx.astype(dx_ref.dtype)
        ds_scr[j * nt + r] = ds * decay + upd

    _tiles(dt_ref, cum_ref, last, t, tile)
    ddt_ref[0, 0] += ddtc_scr[...].T
    dcum_ref[0, 0] += dcumc_scr[...].T

    @pl.when(j == nhb - 1)
    def _group_out():
        dgm = dg_scr[...].astype(mx)
        dc_ref[0, 0] = (dc_scr[...] + _dot(dgm, b_ref[0, 0].astype(mx),
                                           ((1,), (0,)))).astype(dc_ref.dtype)
        db_ref[0, 0] = (db_scr[...] + _dot(dgm, c_ref[0, 0].astype(mx),
                                           ((0,), (0,)))).astype(db_ref.dtype)


# -- calls --------------------------------------------------------------------
def _head_block(hg: int, p: int) -> int:
    """Heads a grid step takes: the largest divisor of ``hg`` up to 16
    whose lanes (hb·P) fill whole 128-lane tiles, else all ``hg``. At
    mamba2-370m's widths on a v5e, 16 heads a step against 32 cost 2% more
    kernel time and compile in a third of the time."""
    fits = [d for d in range(1, min(hg, _MAX_HEAD_BLOCK) + 1)
            if hg % d == 0 and d * p % 128 == 0]
    return max(fits) if fits else hg


def _geometry(x, B, chunk, reverse=False):
    """Sizes, grid and BlockSpecs by role for x (B, S, H, P) and B
    (B, G, S, N), over the grid (b, g, chunk, head block). ``t`` heads
    share a tile of W = t·P lanes (t = 128/P for narrow heads)."""
    b, s, h, p = x.shape
    g, n = B.shape[1], B.shape[3]
    hg, nc = h // g, s // chunk
    hb = _head_block(hg, p)
    nhb = hg // hb
    t = 128 // p if p < 128 and 128 % p == 0 and hb % (128 // p) == 0 else 1
    c = (lambda ci: nc - 1 - ci) if reverse else (lambda ci: ci)
    q = chunk
    specs = {
        "heads": pl.BlockSpec(
            (1, q, hb * p), lambda bi, gi, ci, j: (bi, c(ci), gi * nhb + j)),
        "row": pl.BlockSpec(
            (1, 1, hb, q), lambda bi, gi, ci, j: (bi, gi * nhb + j, 0, c(ci))),
        "group": pl.BlockSpec(
            (1, 1, q, n), lambda bi, gi, ci, j: (bi, gi, c(ci), 0)),
        "starts": pl.BlockSpec(
            (1, 1, n, hb * p),
            lambda bi, gi, ci, j: (bi, c(ci), 0, gi * nhb + j)),
        "state": pl.BlockSpec(
            (1, hg // t, n, t * p), lambda bi, gi, ci, j: (bi, gi, 0, 0)),
    }
    return SimpleNamespace(b=b, s=s, h=h, p=p, g=g, n=n, hg=hg, nc=nc, hb=hb,
                           nhb=nhb, t=t, grid=(b, g, nc, nhb), specs=specs)


def _params():
    return pltpu.CompilerParams(dimension_semantics=(
        "parallel", "parallel", "arbitrary", "arbitrary"))


def _state_to_tiles(state, t):
    """(B, H, N, P) → (B, H/t, N, t·P): each tile's t heads side by side."""
    b, h, n, p = state.shape
    return state.reshape(b, h // t, t, n, p).transpose(0, 1, 3, 2, 4).reshape(
        b, h // t, n, t * p)


def _state_from_tiles(state, t):
    b, ht, n, w = state.shape
    return state.reshape(b, ht, n, t, w // t).transpose(0, 1, 3, 2, 4).reshape(
        b, ht * t, n, w // t)


def _forward(x, dt, cum, B, C, chunk, interpret, save_starts):
    geo = _geometry(x, B, chunk)
    sp = geo.specs
    out_specs = [sp["heads"], sp["state"]]
    out_shape = [jax.ShapeDtypeStruct((geo.b, geo.s, geo.h * geo.p),
                                      x.dtype),
                 jax.ShapeDtypeStruct((geo.b, geo.h // geo.t, geo.n,
                                       geo.t * geo.p), F32)]
    if save_starts:
        out_specs.append(sp["starts"])
        out_shape.append(jax.ShapeDtypeStruct(
            (geo.b, geo.nc, geo.n, geo.h * geo.p), F32))
    with jax.named_scope("ssd"):
        out = pl.pallas_call(
            functools.partial(_fwd_kernel, t=geo.t, p=geo.p),
            grid=geo.grid,
            in_specs=[sp["heads"], sp["row"], sp["row"], sp["group"],
                      sp["group"]],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((geo.hg // geo.t, geo.n,
                                        geo.t * geo.p), F32),
                            pltpu.VMEM((chunk, chunk), F32)],
            compiler_params=_params(),
            interpret=interpret,
            name="ssd_fwd",
        )(x.reshape(geo.b, geo.s, -1), dt, cum, B, C)
    return (out[0].reshape(x.shape), _state_from_tiles(out[1], geo.t),
            *out[2:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, dt, cum, B, C, chunk, interpret):
    y, state = _forward(x, dt, cum, B, C, chunk, interpret, False)
    return y, state


def _ssd_fwd(x, dt, cum, B, C, chunk, interpret):
    y, state, starts = _forward(x, dt, cum, B, C, chunk, interpret, True)
    return (y, state), (x, dt, cum, B, C, starts)


def _ssd_bwd(chunk, interpret, res, cts):
    x, dt, cum, B, C, starts = res
    dy, dstate = cts
    geo = _geometry(x, B, chunk, reverse=True)
    sp = geo.specs
    flat = lambda t: t.astype(x.dtype).reshape(geo.b, geo.s, -1)
    rows = jax.ShapeDtypeStruct(dt.shape, F32)
    with jax.named_scope("ssd"):
        dx, ddt, dcum, dB, dC = pl.pallas_call(
            functools.partial(_bwd_kernel, t=geo.t, p=geo.p, nhb=geo.nhb),
            grid=geo.grid,
            in_specs=[sp["heads"], sp["heads"], sp["row"], sp["row"],
                      sp["group"], sp["group"], sp["starts"], sp["state"]],
            out_specs=[sp["heads"], sp["row"], sp["row"], sp["group"],
                       sp["group"]],
            out_shape=[jax.ShapeDtypeStruct((geo.b, geo.s, geo.h * geo.p),
                                            x.dtype), rows, rows,
                       jax.ShapeDtypeStruct(B.shape, B.dtype),
                       jax.ShapeDtypeStruct(C.shape, C.dtype)],
            scratch_shapes=[pltpu.VMEM((geo.hg // geo.t, geo.n,
                                        geo.t * geo.p), F32),
                            pltpu.VMEM((chunk, chunk), F32),
                            pltpu.VMEM((chunk, chunk), F32),
                            pltpu.VMEM((chunk, geo.n), F32),
                            pltpu.VMEM((chunk, geo.n), F32),
                            pltpu.VMEM((chunk, geo.hb), F32),
                            pltpu.VMEM((chunk, geo.hb), F32)],
            compiler_params=_params(),
            interpret=interpret,
            name="ssd_bwd",
        )(flat(x), flat(dy), dt, cum, B, C, starts,
          _state_to_tiles(dstate.astype(F32), geo.t))
    return dx.reshape(x.shape), ddt, dcum, dB, dC


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(x, dt, A, B, C, *, chunk: int = 128, interpret: bool = False):
    """The contract of ref.ssd_chunked, with B and C at group width: x
    (B,S,H,P), dt (B,S,H), A (H,), B/C (B,S,G,N) with G dividing H (head h
    reads group h // (H/G)) → (y (B,S,H,P), final state (B,H,N,P) fp32).
    Differentiable in every input (custom VJP)."""
    b, s, h, p = x.shape
    if s % chunk:            # pad to a chunk multiple; dt=0 ⇒ padded steps
        pad = chunk - s % chunk  # are identity on the state and emit y=0
        padder = lambda t: jnp.pad(t, [(0, 0), (0, pad)] +
                                   [(0, 0)] * (t.ndim - 2))
        y, state = ssd_pallas(padder(x), padder(dt), A, padder(B), padder(C),
                              chunk=chunk, interpret=interpret)
        return y[:, :s], state
    nc = s // chunk
    hb = _head_block(h // B.shape[2], p)
    dtf = dt.astype(F32)
    cum = jnp.cumsum((dtf * A.astype(F32)).reshape(b, nc, chunk, h),
                     axis=2).reshape(b, s, h)
    # (B,S,H) → rows (B,H/hb,hb,S): head h is lane h % hb of block h // hb
    rows = lambda t: t.reshape(b, s, h // hb, hb).transpose(0, 2, 3, 1)
    return _ssd(x, rows(dtf), rows(cum), jnp.moveaxis(B, 2, 1),
                jnp.moveaxis(C, 2, 1), chunk, interpret)
