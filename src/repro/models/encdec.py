"""Seamless-style encoder–decoder backbone.

The speech frontend (mel + conformer conv) is STUBBED: the encoder consumes
precomputed frame embeddings [B, T_frames, d_model].  Encoder layers are
bidirectional self-attn + FFN; decoder layers are causal self-attn +
cross-attn + FFN.  Positional encoding uses RoPE on self-attention (a
backbone-level approximation of the release's conformer relative positions —
noted in DESIGN.md).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.attention import (cross_attention, full_attention,
                                    gqa_attention, gqa_decode, gqa_init,
                                    init_kv_cache, prefill_kv_cache)
from repro.models.common import (Params, apply_rope, dense_init, embed_init,
                                 rmsnorm, rmsnorm_init, rope_cos_sin,
                                 scan_layers_with_cache, softmax_cross_entropy,
                                 stacked_init, text_positions)
from repro.models.mlp import mlp_apply, mlp_init
from repro.models.transformer import ModelBundle


def _enc_layer_init(key, cfg: ArchConfig, dtype):
    ks = jax.random.split(key, 2)
    return {
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "attn": gqa_init(ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                         cfg.resolved_head_dim, dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
        "mlp": mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.act, dtype),
    }


def _dec_layer_init(key, cfg: ArchConfig, dtype):
    ks = jax.random.split(key, 3)
    return {
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "self_attn": gqa_init(ks[0], cfg.d_model, cfg.n_heads,
                              cfg.n_kv_heads, cfg.resolved_head_dim, dtype),
        "ln_x": rmsnorm_init(cfg.d_model, dtype),
        "cross_attn": gqa_init(ks[1], cfg.d_model, cfg.n_heads,
                               cfg.n_kv_heads, cfg.resolved_head_dim, dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
        "mlp": mlp_init(ks[2], cfg.d_model, cfg.d_ff, cfg.act, dtype),
    }


def build_encdec(cfg: ArchConfig, *, param_dtype=jnp.float32,
                 compute_dtype=None, remat: bool = False, impl: str = "auto",
                 cache_dtype=jnp.bfloat16, **_unused) -> ModelBundle:
    compute_dtype = compute_dtype or param_dtype
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim

    def init(key):
        ks = jax.random.split(key, 4)
        return {
            "embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model,
                                param_dtype),
            "enc_layers": stacked_init(
                lambda k: _enc_layer_init(k, cfg, param_dtype), ks[1],
                cfg.n_encoder_layers),
            "enc_norm": rmsnorm_init(cfg.d_model, param_dtype),
            "dec_layers": stacked_init(
                lambda k: _dec_layer_init(k, cfg, param_dtype), ks[2],
                cfg.n_layers),
            "final_norm": rmsnorm_init(cfg.d_model, param_dtype),
            "lm_head": dense_init(ks[3], cfg.d_model, cfg.padded_vocab,
                                  param_dtype),
        }

    def encode(params, frames):
        """frames [B,Tf,d] (stub frontend output) -> encoder states."""
        x = frames.astype(compute_dtype)
        b, t, _ = x.shape
        cos, sin = rope_cos_sin(text_positions(b, t), hd, cfg.rope_theta)

        def body(x, lp):
            h = gqa_attention(lp["attn"], rmsnorm(lp["ln1"], x, cfg.norm_eps),
                              cos, sin, n_heads=H, n_kv_heads=Hkv,
                              head_dim=hd, causal=False, impl=impl)
            x = x + h
            h = mlp_apply(lp["mlp"], rmsnorm(lp["ln2"], x, cfg.norm_eps),
                          cfg.act)
            return x + h

        fn = jax.checkpoint(body) if remat else body

        def step(c, lp):
            return fn(c, lp), None
        x, _ = jax.lax.scan(step, x, params["enc_layers"])
        return rmsnorm(params["enc_norm"], x, cfg.norm_eps)

    def _dec_body_full(enc, cos, sin):
        def body(x, lp):
            h = gqa_attention(lp["self_attn"],
                              rmsnorm(lp["ln1"], x, cfg.norm_eps), cos, sin,
                              n_heads=H, n_kv_heads=Hkv, head_dim=hd,
                              impl=impl)
            x = x + h
            hx = rmsnorm(lp["ln_x"], x, cfg.norm_eps)
            b, te, _ = enc.shape
            ek = (enc @ lp["cross_attn"]["wk"]).reshape(b, te, Hkv, hd)
            ev = (enc @ lp["cross_attn"]["wv"]).reshape(b, te, Hkv, hd)
            h = cross_attention(lp["cross_attn"], hx, ek, ev, None,
                                n_heads=H, n_kv_heads=Hkv, head_dim=hd)
            x = x + h
            h = mlp_apply(lp["mlp"], rmsnorm(lp["ln2"], x, cfg.norm_eps),
                          cfg.act)
            return x + h
        return body

    def loss_fn(params, batch):
        enc = encode(params, batch["frames"])
        tok = batch["tokens"]
        x = params["embed"][tok].astype(compute_dtype)
        b, s, _ = x.shape
        cos, sin = rope_cos_sin(text_positions(b, s), hd, cfg.rope_theta)
        body = _dec_body_full(enc, cos, sin)
        fn = jax.checkpoint(body) if remat else body

        def step(c, lp):
            return fn(c, lp), None
        x, _ = jax.lax.scan(step, x, params["dec_layers"])
        h = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = h @ params["lm_head"]
        return softmax_cross_entropy(logits, batch["labels"],
                                     batch.get("mask"))

    # --------------------------- serving ----------------------------- #

    def init_cache(batch: int, max_len: int, enc_len: int = 0):
        enc_len = enc_len or cfg.frontend_tokens

        def one(_):
            return {
                "self": init_kv_cache(batch, max_len, Hkv, hd, cache_dtype),
                "cross_k": jnp.zeros((batch, enc_len, Hkv, hd), cache_dtype),
                "cross_v": jnp.zeros((batch, enc_len, Hkv, hd), cache_dtype),
            }
        caches = [one(i) for i in range(cfg.n_layers)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *caches)

    def prefill(params, batch):
        enc = encode(params, batch["frames"])
        tok = batch["tokens"]
        x = params["embed"][tok].astype(compute_dtype)
        b, s, _ = x.shape
        max_len = batch.get("max_len", s)
        if isinstance(max_len, jax.Array):
            max_len = int(max_len)
        cos, sin = rope_cos_sin(text_positions(b, s), hd, cfg.rope_theta)
        te = enc.shape[1]

        def body(x, lp, _st):
            h_in = rmsnorm(lp["ln1"], x, cfg.norm_eps)
            h = gqa_attention(lp["self_attn"], h_in, cos, sin, n_heads=H,
                              n_kv_heads=Hkv, head_dim=hd, impl=impl)
            kv = prefill_kv_cache(lp["self_attn"], h_in, cos, sin,
                                  n_heads=H, n_kv_heads=Hkv, head_dim=hd,
                                  max_len=max_len, dtype=cache_dtype)
            x = x + h
            hx = rmsnorm(lp["ln_x"], x, cfg.norm_eps)
            ek = (enc @ lp["cross_attn"]["wk"]).reshape(b, te, Hkv, hd)
            ev = (enc @ lp["cross_attn"]["wv"]).reshape(b, te, Hkv, hd)
            h = cross_attention(lp["cross_attn"], hx, ek, ev, None,
                                n_heads=H, n_kv_heads=Hkv, head_dim=hd)
            x = x + h
            h = mlp_apply(lp["mlp"], rmsnorm(lp["ln2"], x, cfg.norm_eps),
                          cfg.act)
            st = {"self": kv, "cross_k": ek.astype(cache_dtype),
                  "cross_v": ev.astype(cache_dtype)}
            return x + h, st

        dummy = init_cache(b, max_len, te)
        x, cache = scan_layers_with_cache(body, x, params["dec_layers"],
                                          dummy)
        h = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return h[:, -1] @ params["lm_head"], cache

    def decode_step(params, tokens, cache):
        b = tokens.shape[0]
        cur = cache["self"]["pos"][0]
        pos = jnp.broadcast_to(cur, (b, 1)).astype(jnp.int32)
        cos, sin = rope_cos_sin(pos, hd, cfg.rope_theta)
        x = params["embed"][tokens][:, None].astype(compute_dtype)

        def body(x, lp, st):
            h, kv = gqa_decode(lp["self_attn"],
                               rmsnorm(lp["ln1"], x, cfg.norm_eps), st["self"],
                               cos, sin, n_heads=H, n_kv_heads=Hkv,
                               head_dim=hd)
            x = x + h
            hx = rmsnorm(lp["ln_x"], x, cfg.norm_eps)
            h = cross_attention(lp["cross_attn"], hx,
                                st["cross_k"].astype(x.dtype),
                                st["cross_v"].astype(x.dtype), None,
                                n_heads=H, n_kv_heads=Hkv, head_dim=hd)
            x = x + h
            h = mlp_apply(lp["mlp"], rmsnorm(lp["ln2"], x, cfg.norm_eps),
                          cfg.act)
            return x + h, dict(st, self=kv)

        x, new_cache = scan_layers_with_cache(body, x, params["dec_layers"],
                                              cache)
        h = rmsnorm(params["final_norm"], x[:, 0], cfg.norm_eps)
        return h @ params["lm_head"], new_cache

    return ModelBundle(cfg=cfg, init=init, loss_fn=loss_fn, prefill=prefill,
                       decode_step=decode_step, init_cache=init_cache,
                       forward=None)
