"""Pure-jnp oracles for the Mamba-2 SSD (state-space dual) scan.

``ssd_sequential``  — literal per-timestep recurrence (ground truth).
``ssd_chunked``     — the chunked SSD algorithm (Mamba-2 paper §6): quadratic
                      attention-like compute inside chunks, linear state
                      passing between chunks. This is what the model runs
                      on the CPU and under a mesh, and what the Pallas
                      kernels (kernel.py) implement on one TPU device.

Shapes (already projected/conv'd by the caller):
  x  (B, S, H, P)   head channels
  dt (B, S, H)      post-softplus step sizes
  A  (H,)           negative decay rates
  B  (B, S, H, N)   input maps (groups already broadcast to heads)
  C  (B, S, H, N)   output maps
returns y (B, S, H, P), final_state (B, H, N, P)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_sequential(x, dt, A, B, C, initial_state=None):
    b, s, h, p = x.shape
    n = B.shape[-1]
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    Bf, Cf, Af = B.astype(jnp.float32), C.astype(jnp.float32), A.astype(jnp.float32)
    state = (jnp.zeros((b, h, n, p), jnp.float32) if initial_state is None
             else initial_state.astype(jnp.float32))

    def step(state, inp):
        xt, dtt, Bt, Ct = inp                     # (b,h,p),(b,h),(b,h,n)
        decay = jnp.exp(dtt * Af)                 # (b,h)
        upd = jnp.einsum("bhn,bhp->bhnp", Bt * dtt[..., None], xt)
        state = state * decay[..., None, None] + upd
        y = jnp.einsum("bhn,bhnp->bhp", Ct, state)
        return state, y

    xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
          jnp.moveaxis(Bf, 1, 0), jnp.moveaxis(Cf, 1, 0))
    state, ys = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), state


def ssd_chunked(x, dt, A, B, C, chunk: int = 64, initial_state=None):
    b, s, h, p = x.shape
    n = B.shape[-1]
    if s % chunk:            # pad to a chunk multiple; dt=0 ⇒ padded steps
        pad = chunk - s % chunk  # are identity on the state and emit y=0
        padder = lambda t: jnp.pad(t, [(0, 0), (0, pad)] +
                                   [(0, 0)] * (t.ndim - 2))
        y, state = ssd_chunked(padder(x), padder(dt), A, padder(B),
                               padder(C), chunk, initial_state)
        return y[:, :s], state
    nc, q = s // chunk, chunk
    xf = x.astype(jnp.float32).reshape(b, nc, q, h, p)
    dtf = dt.astype(jnp.float32).reshape(b, nc, q, h)
    Bf = B.astype(jnp.float32).reshape(b, nc, q, h, n)
    Cf = C.astype(jnp.float32).reshape(b, nc, q, h, n)
    Af = A.astype(jnp.float32)

    a = dtf * Af                                   # (b,c,q,h) negative
    cum = jnp.cumsum(a, axis=2)                    # inclusive

    # ---- intra-chunk (quadratic within chunk) -----------------------------
    scores = jnp.einsum("bcihn,bcjhn->bchij", Cf, Bf)
    ct = cum.transpose(0, 1, 3, 2)                 # (b,c,h,i)
    iq = jnp.arange(q)
    # mask the exponent, not its exp: above the diagonal cum_i - cum_j > 0
    # overflows to inf, and where()'s gradient would then be inf * 0 = NaN
    seg = jnp.where(iq[:, None] >= iq[None, :],
                    ct[..., :, None] - ct[..., None, :], -jnp.inf)
    L = jnp.exp(seg)                               # (b,c,h,i,j)
    M = scores * L * dtf.transpose(0, 1, 3, 2)[..., None, :]  # dt_j
    y_intra = jnp.einsum("bchij,bcjhp->bcihp", M, xf)

    # ---- chunk summaries ----------------------------------------------------
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)            # (b,c,j,h)
    Bx = jnp.einsum("bcjhn,bcjhp->bchnp",
                    Bf * (dtf * decay_to_end)[..., None], xf)  # per-chunk state inject
    chunk_decay = jnp.exp(cum[:, :, -1, :])                    # (b,c,h)
    state0 = (jnp.zeros((b, h, n, p), jnp.float32) if initial_state is None
              else initial_state.astype(jnp.float32))

    def step(state, inp):
        bx_c, cd_c, c_c, cum_c = inp
        # y from carried-in state
        cin = c_c * jnp.exp(cum_c)[..., None]                  # (b,i,h,n)
        y_inter = jnp.einsum("bihn,bhnp->bihp", cin, state)
        state = state * cd_c[:, :, None, None] + bx_c
        return state, y_inter

    xs = (jnp.moveaxis(Bx, 1, 0), jnp.moveaxis(chunk_decay, 1, 0),
          jnp.moveaxis(Cf, 1, 0), jnp.moveaxis(cum, 1, 0))
    state, y_inter = jax.lax.scan(step, state0, xs)
    y = y_intra + jnp.moveaxis(y_inter, 0, 1)
    return y.reshape(b, s, h, p).astype(x.dtype), state


def ssd_decode_step(state, x_t, dt_t, A, B_t, C_t):
    """One recurrent step. state (B,H,N,P) fp32; x_t (B,H,P); dt_t (B,H);
    B_t/C_t (B,H,N). Returns (y (B,H,P), new_state)."""
    dtf = dt_t.astype(jnp.float32)
    decay = jnp.exp(dtf * A.astype(jnp.float32))
    upd = jnp.einsum("bhn,bhp->bhnp",
                     B_t.astype(jnp.float32) * dtf[..., None],
                     x_t.astype(jnp.float32))
    state = state * decay[..., None, None] + upd
    y = jnp.einsum("bhn,bhnp->bhp", C_t.astype(jnp.float32), state)
    return y.astype(x_t.dtype), state
