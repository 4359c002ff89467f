"""The FLOP counts against hand counts on the small configurations."""
import pytest

from chipbench import flops
from repro import configs


def test_ssd_pairs():
    assert flops.ssd_pairs(4, 4) == 10
    assert flops.ssd_pairs(6, 4) == 10 + 3
    assert flops.ssd_pairs(3, 4) == 6


def test_mamba2_train_by_hand():
    cfg = configs.get_reduced("mamba2-370m")      # d 64, di 128, H 8, N 16, P 16
    d, di, h, n, p, k, q, L, V = 64, 128, 8, 16, 16, 4, 16, 2, 512
    assert (cfg.d_inner, cfg.ssm_heads) == (di, h)
    S = 40                                        # 2 full chunks of 16 and 8
    proj = 2 * S * (d * (2 * di + 2 * n + h) + di * d)
    conv = 2 * S * k * (di + 2 * n)
    pairs = 2 * (16 * 17 // 2) + 8 * 9 // 2
    ssd = h * pairs * (2 * n + 2 * p) + h * S * 4 * n * p
    head = 2 * S * d * V
    fwd = L * (proj + conv + ssd) + head
    assert flops.model_flops(cfg, "prefill", S) == fwd
    assert flops.model_flops(cfg, "train", S) == 3 * fwd
    dec = L * (2 * (d * (2 * di + 2 * n + h) + di * d) + 2 * k * (di + 2 * n)
               + h * 4 * n * p) + 2 * d * V
    assert flops.model_flops(cfg, "decode", 1, 7) == dec


def test_hymba_prefill_by_hand():
    cfg = configs.get_reduced("hymba-1.5b")       # 3 layers, full at 0 and 2, window 8
    d, hq, hk, dh, f, m, V = 64, 4, 2, 16, 128, 4, 512
    S = 12
    attn_proj = 2 * S * d * (hq * dh + 2 * hk * dh) + 2 * S * hq * dh * d
    full_pairs = sum(m + i + 1 for i in range(S))
    swa_pairs = sum(m + min(i + 1, 8) for i in range(S))
    mlp = 6 * S * d * f
    ssm = flops.mamba2_seq(cfg, S)
    per = lambda pairs: attn_proj + 4 * dh * hq * pairs + ssm + mlp
    want = 2 * per(full_pairs) + per(swa_pairs) + 2 * S * d * V
    assert flops.model_flops(cfg, "prefill", S) == want
    # decode at position 10: the full layers attend 4 + 11, the window 4 + 8
    dec = lambda pairs: (flops.attention_proj(cfg, 1) + 4 * dh * hq * pairs
                         + flops.mamba2_decode(cfg) + 6 * d * f)
    assert flops.model_flops(cfg, "decode", 1, 10) == 2 * dec(15) + dec(12) + 2 * d * V


def test_serve_batch_counts_real_rows_and_served_tokens():
    cfg = configs.get_reduced("hymba-1.5b")
    one = flops.model_flops(cfg, "prefill", 5) + sum(
        flops.model_flops(cfg, "decode", 1, 5 + t) for t in range(3))
    assert flops.serve_batch_flops(cfg, [5, 5], 4) == pytest.approx(2 * one)


def test_peaks_known_and_unknown():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v99")
