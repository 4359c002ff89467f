"""Reference of the ``ssm`` family (Mamba-2, arXiv:2405.21060): a stack of
pre-norm Mamba-2 mixers with residuals, then a final RMSNorm and the
output head. No attention and no MLP, as published."""
from __future__ import annotations

from . import common as c


def forward(params, tokens, cfg, nm: c.Numerics, at=None):
    """Logits (S, V) of one sequence, or only at positions ``at``;
    ``params`` as ``common.store`` keeps them."""
    h = c.embed(params, tokens)

    def layer(h, lp):
        return h + c.mamba2(c.rms_norm(h, lp["norm"], cfg.norm_eps),
                            lp["ssm"], cfg, nm)

    h = c.scan_layers(layer, h, params["layers"])
    return c.logits(h if at is None else h[at], params, cfg, nm)


def flops(cfg, kind: str, seq: int, pos: int = 0) -> float:
    """Model FLOPs of one sequence: ``train`` and ``prefill`` over ``seq``
    tokens (training counts forward and backward, 3x), ``decode`` one token
    at position ``pos``."""
    from .. import flops as f
    L = cfg.num_layers
    if kind == "decode":
        return L * f.mamba2_decode(cfg) + f.head(cfg, 1)
    fwd = L * f.mamba2_seq(cfg, seq) + f.head(cfg, seq)
    return 3 * fwd if kind == "train" else fwd
