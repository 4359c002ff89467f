"""Model assembly: one class serving all six families
(dense / vlm / moe[+mla] / ssm / hybrid / encdec) with three entry points:

  loss_fn(params, batch)            — training loss (CE + MoE aux)
  prefill(params, batch)            — full-sequence forward → (last logits, cache)
  decode_step(params, cache, tok)   — one token with KV/SSM cache

Layers are stacked and consumed by lax.scan (remat per layer); the hybrid
family splits its stack into full-attention and sliding-window sub-stacks so
SWA layers keep O(window) ring caches instead of O(context).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import mamba as mb
from . import mla
from .attention import (attention_decode, attention_prefill, attention_train,
                        update_kv_cache)
from .common import (ArchConfig, ShardCtx, abstract_params, apply_rope,
                     causal_mask, cross_entropy_loss, dp_axes, init_params,
                     rms_norm, swa_mask, unflatten)
from .ffn import ffn_forward
from .moe import moe_forward

PAD_ID = 256
MOE_AUX_WEIGHT = 0.01


def _kv_quantize(t):
    """Per-token-per-head absmax int8: t (..., dh) -> (int8 codes, f32 scale
    over the dh axis)."""
    tf = t.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(tf), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(tf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def _kv_dequantize(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)
            ).astype(dtype)


# ---------------------------------------------------------------------------
def _tree_slice(tree, start: int, size: int):
    return jax.tree.map(lambda a: jax.lax.slice_in_dim(a, start, start + size,
                                                       axis=0), tree)


def _hybrid_plan(cfg: ArchConfig):
    """Execution order of (kind, index-within-stack, count) segments."""
    full = set(cfg.full_attn_layers)
    plan, i_full, i_swa = [], 0, 0
    run = 0
    for layer in range(cfg.num_layers):
        if layer in full:
            if run:
                plan.append(("swa", i_swa, run)); i_swa += run; run = 0
            plan.append(("full", i_full, 1)); i_full += 1
        else:
            run += 1
    if run:
        plan.append(("swa", i_swa, run))
    return plan


class Model:
    def __init__(self, cfg: ArchConfig, mesh: Mesh | None = None,
                 parallelism: str = "tp") -> None:
        self.cfg = cfg
        self.mesh = mesh
        self.parallelism = parallelism
        self.ctx = ShardCtx(mesh, cfg, parallelism)
        self._dec_hints = (None, None)   # (batch spec, cache seq spec)

    # -- params ----------------------------------------------------------------
    def init(self, rng):
        return init_params(self.cfg, rng)

    def abstract_params(self):
        return abstract_params(self.cfg, self.mesh, self.parallelism)

    # -- embedding / head -------------------------------------------------------
    @jax.named_scope("embed")
    def _embed(self, params, tokens):
        h = jnp.take(params["embed"], tokens, axis=0)
        return self.ctx.act(h)

    def _fuse_images(self, params, h, image_embeds):
        w1, w2 = params["img_proj"]["w1"], params["img_proj"]["w2"]
        img = jax.nn.gelu((image_embeds.astype(w1.dtype) @ w1)
                          .astype(jnp.float32)).astype(h.dtype) @ w2
        n = img.shape[1]
        return jnp.concatenate([img, h[:, n:]], axis=1)

    @jax.named_scope("head")
    def _logits(self, params, h):
        h = rms_norm(h, params["final_norm"], self.cfg.norm_eps)
        return h @ params["lm_head"]

    # ------------------------------------------------------------------
    # Attention sub-blocks (GQA; qk-norm; meta tokens; SWA)
    # ------------------------------------------------------------------
    def _qkv(self, x, ap, positions=None, rope: bool = True):
        cfg = self.cfg
        b, s, _ = x.shape
        q = (x @ ap["wq"]).reshape(b, s, cfg.n_heads, cfg.d_head)
        k = (x @ ap["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
        v = (x @ ap["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
        if cfg.qk_norm:
            q = rms_norm(q, ap["q_norm"], cfg.norm_eps)
            k = rms_norm(k, ap["k_norm"], cfg.norm_eps)
        if rope and positions is not None:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        return q, k, v

    @jax.named_scope("attention")
    def _attn_full_seq(self, x, ap, positions, mode: str, *, window: int = 0,
                       bidir: bool = False, want_cache: bool = False):
        """Self-attention over a full sequence (train or prefill)."""
        cfg, ctx = self.cfg, self.ctx
        b, s, _ = x.shape
        q, k, v = self._qkv(x, ap, positions)
        cache = None
        if want_cache:
            if cfg.kv_quant:
                kq, ks = _kv_quantize(k)
                vq, vs = _kv_quantize(v)
                cache = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
            else:
                cache = {"k": k, "v": v}
        prefix = 0
        if cfg.meta_tokens:
            mk = jnp.broadcast_to(ap["meta_k"][None], (b,) + ap["meta_k"].shape)
            mv = jnp.broadcast_to(ap["meta_v"][None], (b,) + ap["meta_v"].shape)
            k = jnp.concatenate([mk.astype(k.dtype), k], axis=1)
            v = jnp.concatenate([mv.astype(v.dtype), v], axis=1)
            prefix = cfg.meta_tokens
        if mode == "train" or bidir:
            if bidir:
                mask = jnp.ones((s, k.shape[1]), bool)
            else:
                base = (swa_mask(s, s, window) if window
                        else causal_mask(s, s))
                if prefix:
                    mask = jnp.concatenate(
                        [jnp.ones((s, prefix), bool), base], axis=1)
                else:
                    mask = base
            o = attention_train(q, k, v, mask, ctx)
        else:
            o = attention_prefill(q, k, v, ctx, window=window, prefix=prefix)
        return o.reshape(b, s, -1) @ ap["wo"], cache

    def _decode_shard_hints(self, batch: int):
        """Mirror of cache_template's layout decision, used to pin the
        flash-decode sharding pattern (see attention_decode docstring)."""
        mesh = self.mesh
        if mesh is None:
            return (None, None)
        dp = self.ctx.dp
        dp_total = 1
        for a in dp:
            dp_total *= mesh.shape[a]
        bshard = dp if (batch % max(dp_total, 1) == 0
                        and batch >= dp_total) else None
        if self.parallelism == "fsdp":
            seq = None if bshard is not None else ("data", "model")
        elif bshard is None:
            seq = ("data", "model")
        else:
            seq = "model" if not self.ctx.kv_head_sharded else None
        return (bshard, seq)

    @jax.named_scope("attention")
    def _attn_decode(self, x, ap, cache_l, pos, *, window: int = 0):
        """One-token self-attention against a cache (ring buffer when SWA)."""
        cfg, ctx = self.cfg, self.ctx
        bspec, seq_spec = self._dec_hints
        b = x.shape[0]
        positions = jnp.full((b, 1), pos)
        q, k_new, v_new = self._qkv(x, ap, positions)
        ring = window if (window and cache_l["k"].shape[1] == window) else 0
        if cfg.kv_quant:
            kq, ks = _kv_quantize(k_new)
            vq, vs = _kv_quantize(v_new)
            idx = pos % ring if ring else pos
            kc = jax.lax.dynamic_update_slice_in_dim(cache_l["k"], kq, idx, 1)
            vc = jax.lax.dynamic_update_slice_in_dim(cache_l["v"], vq, idx, 1)
            ksc = jax.lax.dynamic_update_slice_in_dim(
                cache_l["k_scale"], ks, idx, 1)
            vsc = jax.lax.dynamic_update_slice_in_dim(
                cache_l["v_scale"], vs, idx, 1)
            new_cache = {"k": kc, "v": vc, "k_scale": ksc, "v_scale": vsc}
            # dequant at read; on TPU this fuses into the decode kernel's
            # HBM->VMEM stream (the Pallas decode kernel reads int8 tiles)
            k_cache = _kv_dequantize(kc, ksc, x.dtype)
            v_cache = _kv_dequantize(vc, vsc, x.dtype)
        else:
            k_cache, v_cache = update_kv_cache(cache_l["k"], cache_l["v"],
                                               k_new, v_new, pos,
                                               ring_window=ring)
            new_cache = {"k": k_cache, "v": v_cache}
        if cfg.meta_tokens:
            mk = jnp.broadcast_to(ap["meta_k"][None], (b,) + ap["meta_k"].shape)
            mv = jnp.broadcast_to(ap["meta_v"][None], (b,) + ap["meta_v"].shape)
            m = cfg.meta_tokens
            smax = k_cache.shape[1]
            kj = jnp.concatenate([mk.astype(k_cache.dtype), k_cache], axis=1)
            vj = jnp.concatenate([mv.astype(v_cache.dtype), v_cache], axis=1)
            j = jnp.arange(m + smax)
            if ring:
                tail_ok = (j - m) < jnp.minimum(pos + 1, smax)
            else:
                tail_ok = (j - m) <= pos
                if window:
                    tail_ok &= (pos - (j - m)) < window
            valid = (j < m) | tail_ok
            o = attention_decode(q, kj, vj, pos, ctx, valid=valid,
                                 bspec=bspec, seq_spec=seq_spec)
        else:
            o = attention_decode(q, k_cache, v_cache, pos, ctx,
                                 window=0 if ring else window, ring=bool(ring),
                                 bspec=bspec, seq_spec=seq_spec)
        return o.reshape(b, 1, -1) @ ap["wo"], new_cache

    # ------------------------------------------------------------------
    # Per-family blocks. Each returns (h, extras).
    # ------------------------------------------------------------------
    @jax.named_scope("mlp")
    def _ffn(self, x, fp):
        return ffn_forward(x, fp, self.cfg.ffn, self.ctx)

    def _block_dense(self, h, lp, positions, mode, want_cache=False,
                     window=0, bidir=False):
        cfg, ctx = self.cfg, self.ctx
        x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        attn, cache = self._attn_full_seq(x, lp["attn"], positions, mode,
                                          window=window, bidir=bidir,
                                          want_cache=want_cache)
        h = ctx.act(h + attn)
        x = rms_norm(h, lp["ffn_norm"], cfg.norm_eps)
        h = ctx.act(h + self._ffn(x, lp["ffn"]))
        return h, cache

    def _block_dense_decode(self, h, lp, cache_l, pos, window=0):
        cfg, ctx = self.cfg, self.ctx
        x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        attn, new_cache = self._attn_decode(x, lp["attn"], cache_l, pos,
                                            window=window)
        h = ctx.act(h + attn)
        x = rms_norm(h, lp["ffn_norm"], cfg.norm_eps)
        h = ctx.act(h + self._ffn(x, lp["ffn"]))
        return h, new_cache

    def _block_moe(self, h, lp, positions, mode, want_cache=False):
        cfg, ctx = self.cfg, self.ctx
        x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        if cfg.kv_lora:
            with jax.named_scope("attention"):
                attn, cache = mla.mla_full(x, lp["attn"], cfg, ctx, positions,
                                           mode)
            if not want_cache:
                cache = None
        else:
            attn, cache = self._attn_full_seq(x, lp["attn"], positions, mode,
                                              want_cache=want_cache)
        h = ctx.act(h + attn)
        x = rms_norm(h, lp["ffn_norm"], cfg.norm_eps)
        with jax.named_scope("mlp"):
            y, aux = moe_forward(x, lp["moe"], cfg, ctx)
        h = ctx.act(h + y)
        return h, (cache, aux)

    def _block_moe_decode(self, h, lp, cache_l, pos):
        cfg, ctx = self.cfg, self.ctx
        x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        if cfg.kv_lora:
            with jax.named_scope("attention"):
                attn, new_cache = mla.mla_decode(x, lp["attn"], cfg, ctx,
                                                 cache_l, pos)
        else:
            attn, new_cache = self._attn_decode(x, lp["attn"], cache_l, pos)
        h = ctx.act(h + attn)
        x = rms_norm(h, lp["ffn_norm"], cfg.norm_eps)
        with jax.named_scope("mlp"):
            y, _ = moe_forward(x, lp["moe"], cfg, ctx)
        h = ctx.act(h + y)
        return h, new_cache

    def _block_ssm(self, h, lp, mode):
        cfg, ctx = self.cfg, self.ctx
        x = rms_norm(h, lp["norm"], cfg.norm_eps)
        if mode == "prefill":
            y, cache = mb.mamba_prefill(x, lp["ssm"], cfg, ctx)
            return ctx.act(h + y), cache
        y = mb.mamba_forward(x, lp["ssm"], cfg, ctx)
        return ctx.act(h + y), None

    def _block_ssm_decode(self, h, lp, cache_l, pos):
        cfg, ctx = self.cfg, self.ctx
        x = rms_norm(h, lp["norm"], cfg.norm_eps)
        y, new_cache = mb.mamba_decode(x, lp["ssm"], cfg, ctx, cache_l)
        return ctx.act(h + y), new_cache

    def _block_hybrid(self, h, lp, positions, mode, *, window, want_cache):
        cfg, ctx = self.cfg, self.ctx
        x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        attn, acache = self._attn_full_seq(x, lp["attn"], positions, mode,
                                           window=window,
                                           want_cache=want_cache)
        if mode == "prefill":
            sy, scache = mb.mamba_prefill(x, lp["ssm"], cfg, ctx)
        else:
            sy, scache = mb.mamba_forward(x, lp["ssm"], cfg, ctx), None
        f = lp["fuse"]
        fused = 0.5 * (rms_norm(attn, f["attn_out_norm"], cfg.norm_eps)
                       * f["beta_attn"]
                       + rms_norm(sy, f["ssm_out_norm"], cfg.norm_eps)
                       * f["beta_ssm"])
        h = ctx.act(h + fused)
        x = rms_norm(h, lp["ffn_norm"], cfg.norm_eps)
        h = ctx.act(h + self._ffn(x, lp["ffn"]))
        cache = None
        if want_cache:
            if window:      # keep only the trailing ring window
                s = acache["k"].shape[1]
                w = min(window, s)
                acache = {"k": acache["k"][:, s - w:],
                          "v": acache["v"][:, s - w:]}
            cache = {"attn": acache, "ssm": scache}
        return h, cache

    def _block_hybrid_decode(self, h, lp, cache_l, pos, *, window):
        cfg, ctx = self.cfg, self.ctx
        x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        attn, new_ac = self._attn_decode(x, lp["attn"], cache_l["attn"], pos,
                                         window=window)
        sy, new_sc = mb.mamba_decode(x, lp["ssm"], cfg, ctx, cache_l["ssm"])
        f = lp["fuse"]
        fused = 0.5 * (rms_norm(attn, f["attn_out_norm"], cfg.norm_eps)
                       * f["beta_attn"]
                       + rms_norm(sy, f["ssm_out_norm"], cfg.norm_eps)
                       * f["beta_ssm"])
        h = ctx.act(h + fused)
        x = rms_norm(h, lp["ffn_norm"], cfg.norm_eps)
        h = ctx.act(h + self._ffn(x, lp["ffn"]))
        return h, {"attn": new_ac, "ssm": new_sc}

    def _block_encdec_dec(self, h, lp, enc_out, positions, mode,
                          want_cache=False):
        cfg, ctx = self.cfg, self.ctx
        x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        attn, cache = self._attn_full_seq(x, lp["attn"], positions, mode,
                                          want_cache=want_cache)
        h = ctx.act(h + attn)
        x = rms_norm(h, lp["cross_norm"], cfg.norm_eps)
        b, s, _ = x.shape
        with jax.named_scope("attention"):
            q = (x @ lp["cross"]["wq"]).reshape(b, s, cfg.n_heads, cfg.d_head)
            ek = (enc_out @ lp["cross"]["wk"]).reshape(
                b, -1, cfg.n_kv_heads, cfg.d_head)
            ev = (enc_out @ lp["cross"]["wv"]).reshape(
                b, -1, cfg.n_kv_heads, cfg.d_head)
            mask = jnp.ones((s, ek.shape[1]), bool)
            cross = attention_train(q, ek, ev, mask, ctx)
            cross = cross.reshape(b, s, -1) @ lp["cross"]["wo"]
        h = ctx.act(h + cross)
        x = rms_norm(h, lp["ffn_norm"], cfg.norm_eps)
        h = ctx.act(h + self._ffn(x, lp["ffn"]))
        if want_cache:
            cache = {"self": cache, "cross_k": ek, "cross_v": ev}
        return h, cache

    def _block_encdec_dec_decode(self, h, lp, cache_l, pos):
        cfg, ctx = self.cfg, self.ctx
        x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        attn, new_self = self._attn_decode(x, lp["attn"], cache_l["self"], pos)
        h = ctx.act(h + attn)
        x = rms_norm(h, lp["cross_norm"], cfg.norm_eps)
        b = x.shape[0]
        bspec, seq_spec = self._dec_hints
        with jax.named_scope("attention"):
            q = (x @ lp["cross"]["wq"]).reshape(b, 1, cfg.n_heads, cfg.d_head)
            o = attention_decode(q, cache_l["cross_k"], cache_l["cross_v"],
                                 cache_l["cross_k"].shape[1] - 1, ctx,
                                 bspec=bspec, seq_spec=seq_spec)
            o = o.reshape(b, 1, -1) @ lp["cross"]["wo"]
        h = ctx.act(h + o)
        x = rms_norm(h, lp["ffn_norm"], cfg.norm_eps)
        h = ctx.act(h + self._ffn(x, lp["ffn"]))
        return h, {"self": new_self, "cross_k": cache_l["cross_k"],
                   "cross_v": cache_l["cross_v"]}

    # ------------------------------------------------------------------
    # Scans
    # ------------------------------------------------------------------
    def _scan(self, body, h, layer_tree, extra_xs=None):
        if extra_xs is None:
            xs = layer_tree
        else:
            xs = (layer_tree, extra_xs)

        def pinned(carry, x):
            if self.parallelism == "fsdp":
                x = jax.tree.map(self.ctx.layer_param, x)
            return body(carry, x)

        wrapped = jax.checkpoint(pinned)
        with jax.named_scope("layer_stack"):
            return jax.lax.scan(wrapped, h, xs)

    # ------------------------------------------------------------------
    # Full-sequence forward (train / prefill)
    # ------------------------------------------------------------------
    def forward(self, params, batch, mode: str = "train"):
        """Returns (logits, extras) where extras = {'aux': scalar,
        'cache': pytree or None}."""
        cfg, ctx = self.cfg, self.ctx
        tokens = batch["tokens"] if mode != "train" else batch["tokens"][:, :-1]
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        h = self._embed(params, tokens)
        if cfg.family == "vlm":
            h = self._fuse_images(params, h, batch["image_embeds"])
        want_cache = mode == "prefill"
        aux_total = jnp.zeros((), jnp.float32)
        cache: dict[str, Any] = {}

        if cfg.family in ("dense", "vlm"):
            def body(hh, lp):
                hh, c = self._block_dense(hh, lp, positions, mode,
                                          want_cache=want_cache,
                                          window=cfg.sliding_window)
                return hh, c
            h, layer_cache = self._scan(body, h, params["layers"])
            cache["layers"] = layer_cache

        elif cfg.family == "moe":
            def body(hh, lp):
                hh, (c, aux) = self._block_moe(hh, lp, positions, mode,
                                               want_cache=want_cache)
                return hh, (c, aux)
            h, (layer_cache, auxes) = self._scan(body, h, params["layers"])
            aux_total = jnp.sum(auxes)
            cache["layers"] = layer_cache

        elif cfg.family == "ssm":
            def body(hh, lp):
                return self._block_ssm(hh, lp, mode)
            h, layer_cache = self._scan(body, h, params["layers"])
            cache["layers"] = layer_cache

        elif cfg.family == "hybrid":
            caches_full, caches_swa = [], []
            for kind, idx, count in _hybrid_plan(cfg):
                stack = params["layers_full" if kind == "full" else "layers_swa"]
                seg = _tree_slice(stack, idx, count)
                window = 0 if kind == "full" else cfg.sliding_window
                def body(hh, lp, _w=window):
                    return self._block_hybrid(hh, lp, positions, mode,
                                              window=_w,
                                              want_cache=want_cache)
                h, seg_cache = self._scan(body, h, seg)
                (caches_full if kind == "full" else caches_swa).append(seg_cache)
            if want_cache:
                cache["layers_full"] = jax.tree.map(
                    lambda *xs: jnp.concatenate(xs, 0), *caches_full)
                cache["layers_swa"] = jax.tree.map(
                    lambda *xs: jnp.concatenate(xs, 0), *caches_swa)

        elif cfg.family == "encdec":
            enc = batch["enc_frames"].astype(cfg.dtype)
            ep = jnp.broadcast_to(jnp.arange(enc.shape[1])[None],
                                  (b, enc.shape[1]))
            def enc_body(hh, lp):
                hh, _ = self._block_dense(hh, lp, ep, "train", bidir=True)
                return hh, None
            enc_h = ctx.act(enc)
            enc_h, _ = self._scan(enc_body, enc_h, params["enc_layers"])
            enc_out = rms_norm(enc_h, params["enc_final_norm"], cfg.norm_eps)

            def dec_body(hh, lp):
                return self._block_encdec_dec(hh, lp, enc_out, positions,
                                              mode, want_cache=want_cache)
            h, layer_cache = self._scan(dec_body, h, params["layers"])
            cache["layers"] = layer_cache
        else:
            raise ValueError(cfg.family)

        logits = self._logits(params, h)
        return logits, {"aux": aux_total, "cache": cache if want_cache else None}

    # ------------------------------------------------------------------
    def loss_fn(self, params, batch):
        cfg = self.cfg
        logits, extras = self.forward(params, batch, mode="train")
        labels = batch["tokens"][:, 1:]
        mask = labels != PAD_ID
        if cfg.family == "vlm":
            pos = jnp.arange(labels.shape[1])[None]
            mask &= pos >= cfg.img_tokens
        with jax.named_scope("head"):
            loss = cross_entropy_loss(logits, labels, mask)
        return loss + MOE_AUX_WEIGHT * extras["aux"], {
            "ce_loss": loss, "aux_loss": extras["aux"]}

    # ------------------------------------------------------------------
    def prefill(self, params, batch, max_len: int | None = None):
        """max_len reserves cache room for subsequent decode_step growth."""
        logits, extras = self.forward(params, batch, mode="prefill")
        cache = extras["cache"]
        s = batch["tokens"].shape[1]
        if max_len is not None and max_len > s:
            cache = self._grow_cache(cache, batch["tokens"].shape[0],
                                     s, max_len)
        cache["pos"] = jnp.asarray(s, jnp.int32)
        return logits[:, -1], cache

    def _grow_cache(self, cache, batch_size: int, s: int, max_len: int):
        """Zero-pad sequence axes up to the decode-time cache template
        (ring/SWA and SSM leaves already have their final shapes)."""
        if self.cfg.sliding_window:
            w = self.cfg.sliding_window
            assert s <= w or s % w == 0, \
                "prompt must be <= window or a window multiple (ring layout)"
        target = self.abstract_cache(batch_size, max_len)
        target.pop("pos", None)

        def pad(x, t):
            if tuple(x.shape) == tuple(t.shape):
                return x
            pads = [(0, ts - xs) for xs, ts in zip(x.shape, t.shape)]
            assert all(p[1] >= 0 for p in pads), (x.shape, t.shape)
            return jnp.pad(x, pads)

        return jax.tree.map(pad, cache, target)

    # ------------------------------------------------------------------
    def decode_step(self, params, cache, tokens):
        """tokens: (B,1) — returns (logits (B,V), new_cache)."""
        cfg, ctx = self.cfg, self.ctx
        pos = cache["pos"]
        self._dec_hints = self._decode_shard_hints(tokens.shape[0])
        h = self._embed(params, tokens)
        new_cache: dict[str, Any] = {"pos": pos + 1}

        if cfg.family in ("dense", "vlm"):
            def body(hh, xs):
                lp, cl = xs
                return self._block_dense_decode(hh, lp, cl, pos,
                                                window=cfg.sliding_window)
            h, nc = jax.lax.scan(body, h, (params["layers"], cache["layers"]))
            new_cache["layers"] = nc

        elif cfg.family == "moe":
            def body(hh, xs):
                lp, cl = xs
                return self._block_moe_decode(hh, lp, cl, pos)
            h, nc = jax.lax.scan(body, h, (params["layers"], cache["layers"]))
            new_cache["layers"] = nc

        elif cfg.family == "ssm":
            def body(hh, xs):
                lp, cl = xs
                return self._block_ssm_decode(hh, lp, cl, pos)
            h, nc = jax.lax.scan(body, h, (params["layers"], cache["layers"]))
            new_cache["layers"] = nc

        elif cfg.family == "hybrid":
            nc_full, nc_swa = [], []
            for kind, idx, count in _hybrid_plan(cfg):
                stack_name = "layers_full" if kind == "full" else "layers_swa"
                seg_p = _tree_slice(params[stack_name], idx, count)
                seg_c = _tree_slice(cache[stack_name], idx, count)
                window = 0 if kind == "full" else cfg.sliding_window
                def body(hh, xs, _w=window):
                    lp, cl = xs
                    return self._block_hybrid_decode(hh, lp, cl, pos, window=_w)
                h, nc = jax.lax.scan(body, h, (seg_p, seg_c))
                (nc_full if kind == "full" else nc_swa).append(nc)
            new_cache["layers_full"] = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, 0), *nc_full)
            new_cache["layers_swa"] = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, 0), *nc_swa)

        elif cfg.family == "encdec":
            def body(hh, xs):
                lp, cl = xs
                return self._block_encdec_dec_decode(hh, lp, cl, pos)
            h, nc = jax.lax.scan(body, h, (params["layers"], cache["layers"]))
            new_cache["layers"] = nc
        else:
            raise ValueError(cfg.family)

        logits = self._logits(params, h)[:, 0]
        return logits, new_cache

    # ------------------------------------------------------------------
    # Cache construction
    # ------------------------------------------------------------------
    def cache_template(self, batch: int, max_len: int):
        """Flat path -> (shape, dtype, PartitionSpec)."""
        cfg = self.cfg
        mesh = self.mesh
        dp = self.ctx.dp
        dp_total = 1
        if mesh is not None:
            for a in dp:
                dp_total *= mesh.shape[a]
        bshard = dp if (mesh is not None and batch % max(dp_total, 1) == 0
                        and batch >= dp_total) else None
        # cache layout: shard the KV head axis only when the KV head count
        # divides TP (repeat-KV archs keep heads whole, shard the seq axis)
        if self.parallelism == "fsdp":
            head_shard = None
            seq_shard = None if bshard is not None else ("data", "model")
        elif bshard is None and mesh is not None:
            seq_shard = ("data", "model")       # batch too small: split seq wide
            head_shard = None
        else:
            seq_shard = "model" if not self.ctx.kv_head_sharded else None
            head_shard = "model" if self.ctx.kv_head_sharded else None
        t = cfg.dtype
        out: dict[str, tuple] = {"pos": ((), jnp.int32, P())}

        def kv(prefix, L, s_len, n_kv, dh, seq_sh):
            kv_t = jnp.int8 if cfg.kv_quant else t
            out[f"{prefix}/k"] = ((L, batch, s_len, n_kv, dh), kv_t,
                                  P(None, bshard, seq_sh, head_shard, None))
            out[f"{prefix}/v"] = ((L, batch, s_len, n_kv, dh), kv_t,
                                  P(None, bshard, seq_sh, head_shard, None))
            if cfg.kv_quant:
                for nm in ("k_scale", "v_scale"):
                    out[f"{prefix}/{nm}"] = (
                        (L, batch, s_len, n_kv), jnp.float32,
                        P(None, bshard, seq_sh, head_shard))

        def ssm(prefix, L):
            h_sh = "model" if (mesh is not None
                               and cfg.ssm_heads % mesh.shape["model"] == 0) else None
            out[f"{prefix}/ssm"] = ((L, batch, cfg.ssm_heads, cfg.ssm_state,
                                     cfg.ssm_headdim), jnp.float32,
                                    P(None, bshard, h_sh, None, None))
            k = cfg.ssm_conv
            gn = cfg.ssm_ngroups * cfg.ssm_state
            di_sh = "model" if (mesh is not None
                                and cfg.ssm_heads % mesh.shape["model"] == 0) else None
            out[f"{prefix}/conv_x"] = ((L, batch, k - 1, cfg.d_inner), t,
                                       P(None, bshard, None, di_sh))
            out[f"{prefix}/conv_B"] = ((L, batch, k - 1, gn), t,
                                       P(None, bshard, None, None))
            out[f"{prefix}/conv_C"] = ((L, batch, k - 1, gn), t,
                                       P(None, bshard, None, None))

        L = cfg.num_layers
        if cfg.family in ("dense", "vlm"):
            kv("layers", L, max_len, cfg.n_kv_heads, cfg.d_head, seq_shard)
        elif cfg.family == "moe":
            if cfg.kv_lora:
                lora_sh = "model" if mesh is not None else None
                out["layers/c_kv"] = ((L, batch, max_len, cfg.kv_lora), t,
                                      P(None, bshard, None, lora_sh))
                out["layers/k_rope"] = ((L, batch, max_len, cfg.qk_rope_dim), t,
                                        P(None, bshard, None, lora_sh))
            else:
                kv("layers", L, max_len, cfg.n_kv_heads, cfg.d_head, seq_shard)
        elif cfg.family == "ssm":
            ssm("layers", L)
        elif cfg.family == "hybrid":
            n_full = len(cfg.full_attn_layers)
            n_swa = L - n_full
            w = min(cfg.sliding_window, max_len)
            kv("layers_full/attn", n_full, max_len, cfg.n_kv_heads,
               cfg.d_head, seq_shard)
            kv("layers_swa/attn", n_swa, w, cfg.n_kv_heads, cfg.d_head,
               "model" if (mesh is not None and not self.ctx.head_sharded
                           and w % mesh.shape["model"] == 0) else None)
            ssm("layers_full/ssm", n_full)
            ssm("layers_swa/ssm", n_swa)
        elif cfg.family == "encdec":
            kv("layers/self", L, max_len, cfg.n_kv_heads, cfg.d_head, seq_shard)
            kv("layers/cross", L, cfg.enc_seq, cfg.n_kv_heads, cfg.d_head,
               seq_shard)
        return out

    def init_cache(self, batch: int, max_len: int):
        tmpl = self.cache_template(batch, max_len)
        flat = {}
        for path, (shape, dtype, _) in tmpl.items():
            flat[path] = jnp.zeros(shape, dtype)
        cache = unflatten(flat)
        return self._fix_cache_layout(cache)

    def abstract_cache(self, batch: int, max_len: int):
        tmpl = self.cache_template(batch, max_len)
        flat = {}
        for path, (shape, dtype, spec) in tmpl.items():
            if self.mesh is None:
                flat[path] = jax.ShapeDtypeStruct(shape, dtype)
            else:
                flat[path] = jax.ShapeDtypeStruct(
                    shape, dtype, sharding=NamedSharding(self.mesh, spec))
        return self._fix_cache_layout(unflatten(flat))

    def _fix_cache_layout(self, cache):
        """encdec stores cross k/v under names matching decode-block access."""
        cfg = self.cfg
        if cfg.family == "encdec":
            lay = cache["layers"]
            cache["layers"] = {"self": lay["self"],
                               "cross_k": lay["cross"]["k"],
                               "cross_v": lay["cross"]["v"]}
        return cache

    def cache_specs(self, batch: int, max_len: int):
        tmpl = self.cache_template(batch, max_len)
        flat = {path: spec for path, (_, _, spec) in tmpl.items()}
        cache = unflatten(flat)
        cfg = self.cfg
        if cfg.family == "encdec":
            lay = cache["layers"]
            cache["layers"] = {"self": lay["self"],
                               "cross_k": lay["cross"]["k"],
                               "cross_v": lay["cross"]["v"]}
        return cache
