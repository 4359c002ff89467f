"""Model FLOPs from a configuration's shapes, and the chips' peaks.

Conventions: a multiply-add is 2 FLOPs; training is 3x the forward pass
(forward, and backward for activations and weights); recomputation is not
counted; only real tokens count (no padding rows, no padded positions);
attention counts the (query, key) pairs actually attended; elementwise
work (norms, activations, softmax) is not counted. The per-family sums
are in ``families/<family>.py``; peaks are in ``peaks.json``.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def ssd_pairs(seq: int, chunk: int) -> int:
    """Causal (i, j) pairs inside the chunks of ``seq`` real positions."""
    full, rest = divmod(seq, chunk)
    return full * chunk * (chunk + 1) // 2 + rest * (rest + 1) // 2


def mamba2_seq(cfg, seq: int) -> float:
    d, di, h = cfg.d_model, cfg.d_inner, cfg.ssm_heads
    n, p, k = cfg.ssm_state, cfg.ssm_headdim, cfg.ssm_conv
    gn = cfg.ssm_ngroups * n
    proj = 2 * seq * (d * (2 * di + 2 * gn + h) + di * d)
    conv = 2 * seq * k * (di + 2 * gn)
    intra = h * ssd_pairs(seq, cfg.ssm_chunk) * (2 * n + 2 * p)
    states = h * seq * 4 * n * p          # B x^T into chunk states, C . state out
    return proj + conv + intra + states


def mamba2_decode(cfg) -> float:
    d, di, h = cfg.d_model, cfg.d_inner, cfg.ssm_heads
    n, p, k = cfg.ssm_state, cfg.ssm_headdim, cfg.ssm_conv
    gn = cfg.ssm_ngroups * n
    return (2 * (d * (2 * di + 2 * gn + h) + di * d) + 2 * k * (di + 2 * gn)
            + h * 4 * n * p)


def attention_proj(cfg, seq: int) -> float:
    d, hq, hk, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    return 2 * seq * d * (hq * dh + 2 * hk * dh) + 2 * seq * hq * dh * d


def _attended(i, window: int, meta: int):
    i = np.asarray(i, np.int64)
    return meta + (np.minimum(i + 1, window) if window else i + 1)


def attention_seq(cfg, seq: int, window: int) -> float:
    pairs = int(_attended(np.arange(seq), window, cfg.meta_tokens).sum())
    return 4 * cfg.d_head * cfg.n_heads * pairs


def attention_decode(cfg, pos: int, window: int) -> float:
    return 4 * cfg.d_head * cfg.n_heads * int(_attended(pos, window, cfg.meta_tokens))


def swiglu(cfg, seq: int) -> float:
    return 6 * seq * cfg.d_model * cfg.d_ff


def head(cfg, seq: int) -> float:
    return 2 * seq * cfg.d_model * cfg.vocab_size


def model_flops(cfg, kind: str, seq: int, pos: int = 0) -> float:
    from .families import family
    return family(cfg.family).flops(cfg, kind, seq, pos)


def serve_batch_flops(cfg, prompt_lens, max_new: int) -> float:
    """A served batch's real rows: prefill over each prompt, then the
    decode steps whose tokens are served (the first token comes from the
    prefill, so ``max_new - 1`` steps)."""
    row: dict[int, float] = {}
    for s in set(prompt_lens):
        row[s] = model_flops(cfg, "prefill", s) + sum(
            model_flops(cfg, "decode", 1, s + t) for t in range(max_new - 1))
    return sum(row[s] for s in prompt_lens)
