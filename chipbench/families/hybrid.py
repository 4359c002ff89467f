"""Reference of the ``hybrid`` family (Hymba, arXiv:2411.13676): each layer
runs attention heads and Mamba-2 heads in parallel on the same normed
input, averages their RMS-normed outputs with learned per-channel scales,
and adds a SwiGLU MLP. Attention has learnable meta-token keys/values in
front of every sequence and a sliding window except in the layers listed
as full.

Departure of the configuration from the paper, noted as the program
states it: the meta tokens enter only as a KV prefix of each attention
layer, not as input positions that also pass through the SSM heads."""
from __future__ import annotations

import jax

from . import common as c


def _plan(cfg):
    """(stack, first index, count, window) segments in layer order."""
    full = set(cfg.full_attn_layers)
    plan, i_full, i_swa = [], 0, 0
    for layer in range(cfg.num_layers):
        if layer in full:
            plan.append(("layers_full", i_full, 1, 0))
            i_full += 1
        elif plan and plan[-1][0] == "layers_swa":
            stack, i0, n, w = plan[-1]
            plan[-1] = (stack, i0, n + 1, w)
            i_swa += 1
        else:
            plan.append(("layers_swa", i_swa, 1, cfg.sliding_window))
            i_swa += 1
    return plan


def forward(params, tokens, cfg, nm: c.Numerics, at=None):
    """Logits (S, V) of one sequence, or only at positions ``at``;
    ``params`` as ``common.store`` keeps them."""
    h = c.embed(params, tokens)
    eps = cfg.norm_eps
    for stack, i0, n, window in _plan(cfg):
        seg = jax.tree.map(lambda x, i0=i0, n=n: x[i0:i0 + n], params[stack])

        def layer(h, lp, window=window):
            x = c.rms_norm(h, lp["attn_norm"], eps)
            a = c.attention(x, lp["attn"], cfg, nm, window)
            s = c.mamba2(x, lp["ssm"], cfg, nm)
            f = lp["fuse"]
            h = h + 0.5 * (c.rms_norm(a, f["attn_out_norm"], eps) * f["beta_attn"]
                           + c.rms_norm(s, f["ssm_out_norm"], eps) * f["beta_ssm"])
            return h + c.swiglu(c.rms_norm(h, lp["ffn_norm"], eps), lp["ffn"], nm)

        h = c.scan_layers(layer, h, seg)
    return c.logits(h if at is None else h[at], params, cfg, nm)


def flops(cfg, kind: str, seq: int, pos: int = 0) -> float:
    """Model FLOPs of one sequence, as ``ssm.flops``. Attention counts
    the (query, key) pairs actually attended: the meta tokens, and the
    causal positions inside the window."""
    from .. import flops as f
    total = 0.0
    for _, _, n, window in _plan(cfg):
        if kind == "decode":
            per = (f.attention_proj(cfg, 1) + f.attention_decode(cfg, pos, window)
                   + f.mamba2_decode(cfg) + f.swiglu(cfg, 1))
        else:
            per = (f.attention_proj(cfg, seq) + f.attention_seq(cfg, seq, window)
                   + f.mamba2_seq(cfg, seq) + f.swiglu(cfg, seq))
        total += n * per
    total += f.head(cfg, 1 if kind == "decode" else seq)
    return 3 * total if kind == "train" else total
