"""Plain reference pieces, in ``jax.numpy``, one sequence (row) at a time.

Nothing here imports the program. The equations are those of the
published layers as the program states them in its own docstrings:
RMSNorm, rotate-half RoPE, grouped-query attention with a learnable KV
prefix (meta tokens) and a sliding window, SwiGLU, and the Mamba-2 mixer
(separate z/x/B/C/dt projections, depthwise causal convolution, SSD,
gated RMSNorm). The SSD is the chunked state-space-dual form of the
Mamba-2 paper (section 6): exact in exact arithmetic, with a chunk of its
own, different from the program's.

``Numerics`` says how a forward pass is computed: the reference is
float32 with ``HIGHEST`` matmul precision (a TPU runs float32 matmuls in
bf16 passes otherwise) on the weights as the configuration stores them;
the control stores every weight matrix in fp8 (e4m3, one scale per output
channel) and computes its matmuls in bfloat16. Weights reach a forward
pass as stored (``store``) and are widened there, a layer at a time
(``load``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG_INF = -1e30
SSD_CHUNK = 128
_KEEP = ("norm", "A_log", "D", "dt_bias", "beta_attn", "beta_ssm")
FP8_MAX = 448.0                              # largest float8_e4m3fn


@dataclass(frozen=True)
class Numerics:
    compute: object = F32
    precision: object = jax.lax.Precision.HIGHEST
    fp8_weights: bool = False


REFERENCE = Numerics()
CONTROL = Numerics(compute=jnp.bfloat16, precision=None, fp8_weights=True)


def _is_matrix(path: str) -> bool:
    last = path.split("/")[-1]
    return not any(last.endswith(k) or last == k for k in _KEEP)


class Q(NamedTuple):
    """A weight stored in fp8 with one float32 scale per output channel."""
    q: jax.Array
    s: jax.Array


def _walk(fn, tree, prefix=""):
    if isinstance(tree, dict):
        return {k: _walk(fn, v, f"{prefix}/{k}" if prefix else k)
                for k, v in tree.items()}
    return fn(prefix, tree)


def store(params, nm: Numerics, dtype):
    """The weights as a run keeps them: in the configuration's ``dtype``,
    or, for the control, every matrix in fp8. Call it as its own jitted
    program, so that the rounding is materialized and cannot be elided."""
    def one(path, w):
        if nm.fp8_weights and _is_matrix(path):
            w = w.astype(F32)
            s = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True),
                            1e-12) / FP8_MAX
            return Q((w / s).astype(jnp.float8_e4m3fn), s)
        return w.astype(dtype)
    return _walk(one, params)


def load(stored):
    """Float32 weights from stored ones (exact for a stored input)."""
    if isinstance(stored, Q):
        return stored.q.astype(F32) * stored.s
    if isinstance(stored, dict):
        return {k: load(v) for k, v in stored.items()}
    return stored.astype(F32)


def mm(a, w, nm: Numerics):
    return jnp.matmul(a.astype(nm.compute), w.astype(nm.compute),
                      precision=nm.precision, preferred_element_type=F32)


def rms_norm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def silu(x):
    return x * jax.nn.sigmoid(x)


def rope(x, theta):
    """x: (S, H, dh) at positions 0..S-1; rotate-half."""
    s, _, dh = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs          # (S, dh/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_conv(x, w):
    """Depthwise causal convolution. x: (S, C), w: (K, C)."""
    k = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(xp[i:i + x.shape[0]] * w[i] for i in range(k))


def ssd(x, dt, A, B, C, chunk: int = SSD_CHUNK):
    """y_t = sum_{s<=t} C_t . B_s exp(sum_{s<r<=t} dt_r A) dt_s x_s.
    x (S,H,P), dt (S,H), A (H,), B/C (S,H,N); float32."""
    s, h, p = x.shape
    n = B.shape[-1]
    pad = -s % chunk
    if pad:
        zp = lambda t: jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1))
        x, dt, B, C = zp(x), zp(dt), zp(B), zp(C)
    nc = x.shape[0] // chunk
    x = x.reshape(nc, chunk, h, p)
    dt = dt.reshape(nc, chunk, h)
    B = B.reshape(nc, chunk, h, n)
    C = C.reshape(nc, chunk, h, n)
    cum = jnp.cumsum(dt * A, axis=1)                           # (c,q,h)
    i = jnp.arange(chunk)
    seg = jnp.where((i[:, None] >= i[None, :])[None, :, :, None],
                    cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf)
    L = jnp.exp(seg)                                           # (c,i,j,h)
    hp = jax.lax.Precision.HIGHEST
    G = jnp.einsum("cihn,cjhn->cijh", C, B, precision=hp)
    y = jnp.einsum("cijh,cjh,cjhp->cihp", G * L, dt, x, precision=hp)
    to_end = jnp.exp(cum[:, -1:, :] - cum)                     # (c,q,h)
    inject = jnp.einsum("cjhn,cjh,cjhp->chnp", B, dt * to_end, x, precision=hp)

    def carry(state, inp):
        inj, decay, c_in, cum_c = inp
        y_in = jnp.einsum("ihn,hnp->ihp", c_in * jnp.exp(cum_c)[..., None],
                          state, precision=hp)
        return state * decay[:, None, None] + inj, y_in

    _, y_in = jax.lax.scan(carry, jnp.zeros((h, n, p), F32),
                           (inject, jnp.exp(cum[:, -1]), C, cum))
    return (y + y_in).reshape(nc * chunk, h, p)[:s]


def mamba2(h, p, cfg, nm: Numerics):
    """The Mamba-2 mixer over one sequence. h: (S, d) float32."""
    nh, hp_, g, n = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_ngroups, cfg.ssm_state
    s = h.shape[0]
    z = mm(h, p["in_z"], nm)
    xs = silu(causal_conv(mm(h, p["in_x"], nm), p["conv_x"]))
    bs = silu(causal_conv(mm(h, p["in_B"], nm), p["conv_B"]))
    cs = silu(causal_conv(mm(h, p["in_C"], nm), p["conv_C"]))
    dt = jax.nn.softplus(mm(h, p["in_dt"], nm) + p["dt_bias"])  # (S,H)
    x = xs.reshape(s, nh, hp_)
    rep = nh // g
    B = jnp.repeat(bs.reshape(s, g, n), rep, axis=1)
    C = jnp.repeat(cs.reshape(s, g, n), rep, axis=1)
    A = -jnp.exp(p["A_log"])
    y = ssd(x, dt, A, B, C) + x * p["D"][None, :, None]
    y = rms_norm(y.reshape(s, -1) * silu(z), p["gate_norm"], cfg.norm_eps)
    return mm(y, p["out_proj"], nm)


def attention(x, p, cfg, nm: Numerics, window: int):
    """Causal GQA over one sequence with meta-token keys/values in front
    (visible to every query) and an optional sliding window."""
    s = x.shape[0]
    hq, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = rope(mm(x, p["wq"], nm).reshape(s, hq, dh), cfg.rope_theta)
    k = rope(mm(x, p["wk"], nm).reshape(s, hk, dh), cfg.rope_theta)
    v = mm(x, p["wv"], nm).reshape(s, hk, dh)
    m = cfg.meta_tokens
    if m:
        k = jnp.concatenate([p["meta_k"], k])
        v = jnp.concatenate([p["meta_v"], v])
    g = hq // hk
    qg = q.reshape(s, hk, g, dh)
    sc = jnp.einsum("qhgd,khd->hgqk", qg.astype(nm.compute), k.astype(nm.compute),
                    precision=nm.precision, preferred_element_type=F32) * dh ** -0.5
    qi = jnp.arange(s)[:, None]
    kj = jnp.arange(k.shape[0])[None, :] - m
    ok = qi >= kj
    if window:
        ok &= qi - kj < window
    ok |= kj < 0
    pr = jax.nn.softmax(jnp.where(ok, sc, NEG_INF), axis=-1)
    o = jnp.einsum("hgqk,khd->qhgd", pr.astype(nm.compute), v.astype(nm.compute),
                   precision=nm.precision, preferred_element_type=F32)
    return mm(o.reshape(s, hq * dh), p["wo"], nm)


def swiglu(x, p, nm: Numerics):
    return mm(silu(mm(x, p["wi_gate"], nm)) * mm(x, p["wi_up"], nm), p["wo"], nm)


def scan_layers(layer, h, stack):
    """Apply ``layer(h, params_of_one_layer)`` over a stacked tree of
    stored weights, widening one layer's at a time, and recomputing each
    layer in the backward pass."""
    body = jax.checkpoint(lambda c, lp: (layer(c, load(lp)), None))
    h, _ = jax.lax.scan(body, h, stack)
    return h


def embed(params, tokens):
    return jnp.take(load(params["embed"]), tokens, axis=0)


def logits(h, params, cfg, nm: Numerics):
    return mm(rms_norm(h, load(params["final_norm"]), cfg.norm_eps),
              load(params["lm_head"]), nm)
