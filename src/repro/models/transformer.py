"""Decoder-LM assembly for every family in the pool.

One ``ModelBundle`` per architecture exposes:
  init(key)                 -> params
  loss_fn(params, batch)    -> (loss, metrics)          [train_* shapes]
  prefill(params, batch)    -> (last_logits, cache)     [prefill_* shapes]
  decode_step(params, tok, cache) -> (logits, cache)    [decode_* shapes]
  init_cache(batch, max_len)-> zeroed cache pytree      [dry-run specs]

Layer stacks are stacked pytrees scanned with ``lax.scan`` (HLO size is
depth-independent); caches are stacked on the same leading layer dim and
threaded through the scan.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import hybrid as hyb
from repro.models import rwkv6 as rwk
from repro.models.attention import (gqa_attention, gqa_decode,
                                    gqa_decode_paged, gqa_init,
                                    gqa_prefill_paged_chunk, init_kv_cache,
                                    init_mla_cache, init_paged_kv,
                                    init_paged_mla, mla_attention,
                                    mla_decode, mla_decode_paged, mla_init,
                                    mla_prefill_paged_chunk,
                                    prefill_kv_cache, mla_prefill_cache)
from repro.models.common import (Params, embed_init, dense_init,
                                 mrope_cos_sin, rmsnorm, rmsnorm_init,
                                 rope_cos_sin, scan_layers_with_cache,
                                 softmax_cross_entropy, stacked_init,
                                 text_positions)
from repro.models.mlp import mlp_apply, mlp_init
from repro.models.moe import moe_apply, moe_init
from repro.telemetry.spans import HEAD_LOSS


# ===================================================================== #
# generic decoder layer (dense / moe x GQA / MLA)
# ===================================================================== #

def _attn_init(key, cfg: ArchConfig, dtype):
    if cfg.kv_lora_rank:
        return mla_init(key, cfg.d_model, cfg.n_heads, cfg.kv_lora_rank,
                        cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                        cfg.v_head_dim, dtype)
    return gqa_init(key, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                    cfg.resolved_head_dim, dtype)


def layer_init(key, cfg: ArchConfig, use_moe: bool, dtype) -> Params:
    ks = jax.random.split(key, 2)
    p = {
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "attn": _attn_init(ks[0], cfg, dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
    }
    if use_moe:
        p["ffn"] = moe_init(ks[1], cfg.d_model, cfg.expert_d_ff or cfg.d_ff,
                            cfg.n_experts, cfg.n_shared_experts, cfg.act,
                            dtype)
    else:
        p["ffn"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.act, dtype)
    return p


def layer_apply(p: Params, x, cos, sin, cfg: ArchConfig, use_moe: bool,
                window: int, impl: str):
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.kv_lora_rank:
        a = mla_attention(p["attn"], h, cos, sin, n_heads=cfg.n_heads,
                          kv_lora=cfg.kv_lora_rank,
                          qk_nope=cfg.qk_nope_head_dim,
                          qk_rope=cfg.qk_rope_head_dim,
                          v_dim=cfg.v_head_dim, eps=cfg.norm_eps)
    else:
        a = gqa_attention(p["attn"], h, cos, sin, n_heads=cfg.n_heads,
                          n_kv_heads=cfg.n_kv_heads,
                          head_dim=cfg.resolved_head_dim, window=window,
                          impl=impl)
    x = x + a
    h = rmsnorm(p["ln2"], x, cfg.norm_eps)
    if use_moe:
        f, aux = moe_apply(p["ffn"], h, n_experts=cfg.n_experts,
                           top_k=cfg.top_k, act=cfg.act,
                           capacity_factor=cfg.capacity_factor)
    else:
        f, aux = mlp_apply(p["ffn"], h, cfg.act), jnp.zeros((), jnp.float32)
    return x + f, aux


def layer_decode(p: Params, x, cache, cos, sin, cfg: ArchConfig,
                 use_moe: bool, rolling: bool):
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.kv_lora_rank:
        a, cache = mla_decode(p["attn"], h, cache, cos, sin,
                              n_heads=cfg.n_heads, kv_lora=cfg.kv_lora_rank,
                              qk_nope=cfg.qk_nope_head_dim,
                              qk_rope=cfg.qk_rope_head_dim,
                              v_dim=cfg.v_head_dim, eps=cfg.norm_eps)
    else:
        a, cache = gqa_decode(p["attn"], h, cache, cos, sin,
                              n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                              head_dim=cfg.resolved_head_dim, rolling=rolling)
    x = x + a
    h = rmsnorm(p["ln2"], x, cfg.norm_eps)
    if use_moe:
        f, _ = moe_apply(p["ffn"], h, n_experts=cfg.n_experts,
                         top_k=cfg.top_k, act=cfg.act,
                           capacity_factor=cfg.capacity_factor)
    else:
        f = mlp_apply(p["ffn"], h, cfg.act)
    return x + f, cache


def _layer_ffn(p: Params, x, cfg: ArchConfig, use_moe: bool):
    h = rmsnorm(p["ln2"], x, cfg.norm_eps)
    if use_moe:
        f, _ = moe_apply(p["ffn"], h, n_experts=cfg.n_experts,
                         top_k=cfg.top_k, act=cfg.act,
                         capacity_factor=cfg.capacity_factor)
    else:
        f = mlp_apply(p["ffn"], h, cfg.act)
    return x + f


def layer_decode_paged(p: Params, x, pages, block_tables, lengths, active,
                       cos, sin, cfg: ArchConfig, use_moe: bool,
                       decode_impl: str):
    """One layer of the paged decode step (per-slot positions)."""
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.kv_lora_rank:
        a, pages = mla_decode_paged(
            p["attn"], h, pages, block_tables, lengths, active, cos, sin,
            n_heads=cfg.n_heads, kv_lora=cfg.kv_lora_rank,
            qk_nope=cfg.qk_nope_head_dim, qk_rope=cfg.qk_rope_head_dim,
            v_dim=cfg.v_head_dim, eps=cfg.norm_eps)
    else:
        a, pages = gqa_decode_paged(
            p["attn"], h, pages, block_tables, lengths, active, cos, sin,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.resolved_head_dim, window=cfg.sliding_window,
            impl=decode_impl)
    return _layer_ffn(p, x + a, cfg, use_moe), pages


def layer_prefill_paged(p: Params, x, pages, block_tables, base, cos, sin,
                        cfg: ArchConfig, use_moe: bool):
    """One layer of one paged-prefill chunk (positions base..base+C-1)."""
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.kv_lora_rank:
        a, pages = mla_prefill_paged_chunk(
            p["attn"], h, pages, block_tables, base, cos, sin,
            n_heads=cfg.n_heads, kv_lora=cfg.kv_lora_rank,
            qk_nope=cfg.qk_nope_head_dim, qk_rope=cfg.qk_rope_head_dim,
            v_dim=cfg.v_head_dim, eps=cfg.norm_eps)
    else:
        a, pages = gqa_prefill_paged_chunk(
            p["attn"], h, pages, block_tables, base, cos, sin,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.resolved_head_dim, window=cfg.sliding_window)
    return _layer_ffn(p, x + a, cfg, use_moe), pages


# ===================================================================== #
# bundle
# ===================================================================== #

@dataclasses.dataclass
class ModelBundle:
    cfg: ArchConfig
    init: Callable
    loss_fn: Callable
    prefill: Callable
    decode_step: Callable
    init_cache: Callable
    # extras
    forward: Optional[Callable] = None
    # paged serving (None for families with constant-size state caches):
    #   init_paged_cache(n_pages, page_size) -> pages pytree [L, ...]
    #   prefill_paged_chunk(params, tokens [B,C], pages, tables, base)
    #       -> (logits [B,C,V], pages)
    #   decode_step_paged(params, tokens [B], pages, tables, lengths,
    #       active) -> (logits [B,V], pages)
    init_paged_cache: Optional[Callable] = None
    prefill_paged_chunk: Optional[Callable] = None
    decode_step_paged: Optional[Callable] = None


def _rope_for(cfg: ArchConfig, positions):
    """positions [B,S] (or [B,S,3] for M-RoPE) -> cos/sin [B,S,hd//2]."""
    hd = cfg.qk_rope_head_dim if cfg.kv_lora_rank else cfg.resolved_head_dim
    if cfg.mrope:
        return mrope_cos_sin(positions, hd, cfg.rope_theta,
                             cfg.mrope_sections)
    return rope_cos_sin(positions, hd, cfg.rope_theta)


def _unembed(params, cfg: ArchConfig, x):
    if cfg.tie_embeddings:
        return x @ params["embed"].T
    return x @ params["lm_head"]


def _split_layers(cfg: ArchConfig) -> Tuple[int, int]:
    """(n_dense_prefix, n_main) — prefix layers use a dense FFN."""
    if cfg.uses_moe and cfg.first_k_dense:
        return cfg.first_k_dense, cfg.n_layers - cfg.first_k_dense
    return 0, cfg.n_layers


def build_decoder_lm(cfg: ArchConfig, *, param_dtype=jnp.float32,
                     compute_dtype=None, remat: bool = False,
                     impl: str = "auto", rolling_decode: bool = False,
                     cache_dtype=jnp.bfloat16,
                     decode_impl: str = "auto") -> ModelBundle:
    """dense / moe / mla / vlm families.

    ``decode_impl`` picks the paged decode-attention kernel
    (kernels/ops.py::flash_decode dispatch): "auto" / "xla" / "pallas" /
    "pallas_interpret".  It only affects decode_step_paged.
    """
    compute_dtype = compute_dtype or param_dtype
    n_pre, n_main = _split_layers(cfg)
    window = cfg.sliding_window

    def init(key) -> Params:
        ks = jax.random.split(key, 4)
        p: Params = {
            "embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model,
                                param_dtype),
            "final_norm": rmsnorm_init(cfg.d_model, param_dtype),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = dense_init(ks[1], cfg.d_model, cfg.padded_vocab,
                                      param_dtype)
        p["layers"] = stacked_init(
            lambda k: layer_init(k, cfg, cfg.uses_moe, param_dtype),
            ks[2], n_main)
        if n_pre:
            p["layers_dense"] = stacked_init(
                lambda k: layer_init(k, cfg, False, param_dtype), ks[3], n_pre)
        return p

    def _stack_forward(params, x, cos, sin):
        """x [B,S,d] -> (hidden, aux_loss)."""
        def body_dense(carry, lp):
            x, aux = carry
            x, a = layer_apply(lp, x, cos, sin, cfg, False, window, impl)
            return (x, aux + a), None

        def body_main(carry, lp):
            x, aux = carry
            x, a = layer_apply(lp, x, cos, sin, cfg, cfg.uses_moe, window,
                               impl)
            return (x, aux + a), None

        carry = (x, jnp.zeros((), jnp.float32))
        if n_pre:
            fn = jax.checkpoint(body_dense) if remat else body_dense
            carry, _ = jax.lax.scan(fn, carry, params["layers_dense"])
        fn = jax.checkpoint(body_main) if remat else body_main
        carry, _ = jax.lax.scan(fn, carry, params["layers"])
        x, aux = carry
        return rmsnorm(params["final_norm"], x, cfg.norm_eps), aux

    def forward(params, embeds, positions):
        cos, sin = _rope_for(cfg, positions)
        h, aux = _stack_forward(params, embeds.astype(compute_dtype), cos, sin)
        return h, aux

    def _embed_batch(params, batch):
        """Returns (embeds [B,S,d], positions, label_offset)."""
        tok_emb = params["embed"][batch["tokens"]]
        if cfg.family == "vlm" and "vision_embeds" in batch:
            v = batch["vision_embeds"].astype(tok_emb.dtype)
            embeds = jnp.concatenate([v, tok_emb], axis=1)
            positions = batch["positions"]        # [B, Nv+St, 3]
            return embeds, positions, v.shape[1]
        if cfg.mrope:
            b, s = batch["tokens"].shape
            pos = text_positions(b, s)
            positions = jnp.stack([pos, pos, pos], axis=-1)
        else:
            positions = text_positions(*batch["tokens"].shape)
        return tok_emb, positions, 0

    def loss_fn(params, batch):
        embeds, positions, off = _embed_batch(params, batch)
        h, aux = forward(params, embeds, positions)
        if off:
            h = h[:, off:]
        with jax.named_scope(HEAD_LOSS):
            logits = _unembed(params, cfg, h)
            loss, metrics = softmax_cross_entropy(logits, batch["labels"],
                                                  batch.get("mask"))
        if cfg.uses_moe:
            aux = aux / max(1, n_main)
            loss = loss + cfg.router_aux_coef * aux
            metrics["aux_loss"] = aux
        metrics["loss"] = loss
        return loss, metrics

    # ------------------------- serving ------------------------------- #

    def init_cache(batch: int, max_len: int):
        def one(_):
            if cfg.kv_lora_rank:
                return init_mla_cache(batch, max_len, cfg.kv_lora_rank,
                                      cfg.qk_rope_head_dim, cache_dtype)
            w = cfg.long_context_window if rolling_decode else 0
            return init_kv_cache(batch, max_len, cfg.n_kv_heads,
                                 cfg.resolved_head_dim, cache_dtype,
                                 rolling=rolling_decode, window=w)
        n_layers = cfg.n_layers
        caches = [one(i) for i in range(n_layers)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *caches)

    def prefill(params, batch):
        """Full-prompt forward; returns (last-position logits, cache)."""
        embeds, positions, off = _embed_batch(params, batch)
        cos, sin = _rope_for(cfg, positions)
        x = embeds.astype(compute_dtype)
        max_len = batch.get("max_len", x.shape[1])
        if isinstance(max_len, jax.Array):
            max_len = int(max_len)

        def make_body(use_moe):
            def body(carry, lp):
                x = carry[0]
                h = rmsnorm(lp["ln1"], x, cfg.norm_eps)
                if cfg.kv_lora_rank:
                    a = mla_attention(
                        lp["attn"], h, cos, sin, n_heads=cfg.n_heads,
                        kv_lora=cfg.kv_lora_rank,
                        qk_nope=cfg.qk_nope_head_dim,
                        qk_rope=cfg.qk_rope_head_dim, v_dim=cfg.v_head_dim,
                        eps=cfg.norm_eps)
                    cache = mla_prefill_cache(lp["attn"], h, cos, sin,
                                              max_len=max_len,
                                              eps=cfg.norm_eps,
                                              dtype=cache_dtype)
                else:
                    a = gqa_attention(lp["attn"], h, cos, sin,
                                      n_heads=cfg.n_heads,
                                      n_kv_heads=cfg.n_kv_heads,
                                      head_dim=cfg.resolved_head_dim,
                                      window=window, impl=impl)
                    w = cfg.long_context_window if rolling_decode else 0
                    cache = prefill_kv_cache(
                        lp["attn"], h, cos, sin, n_heads=cfg.n_heads,
                        n_kv_heads=cfg.n_kv_heads,
                        head_dim=cfg.resolved_head_dim, max_len=max_len,
                        dtype=cache_dtype, rolling=rolling_decode, window=w)
                x = x + a
                h = rmsnorm(lp["ln2"], x, cfg.norm_eps)
                if use_moe:
                    f, _ = moe_apply(lp["ffn"], h, n_experts=cfg.n_experts,
                                     top_k=cfg.top_k, act=cfg.act,
                           capacity_factor=cfg.capacity_factor)
                else:
                    f = mlp_apply(lp["ffn"], h, cfg.act)
                return (x + f, None), cache
            return body

        # dense prefix then main stack, collecting caches stacked on layer dim
        caches = []
        x_c = (x, None)
        if n_pre:
            x_c, pre_caches = jax.lax.scan(make_body(False), x_c,
                                           params["layers_dense"])
            caches.append(pre_caches)
        x_c, main_caches = jax.lax.scan(make_body(cfg.uses_moe), x_c,
                                        params["layers"])
        caches.append(main_caches)
        cache = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *caches) \
            if len(caches) > 1 else caches[0]
        h = rmsnorm(params["final_norm"], x_c[0], cfg.norm_eps)
        logits = _unembed(params, cfg, h[:, -1])
        return logits, cache

    def decode_step(params, tokens, cache):
        """tokens [B] int32 -> (logits [B,V], cache)."""
        b = tokens.shape[0]
        # every layer shares the same position counter (stacked pos [L])
        cur = cache["pos"][0]
        if cfg.mrope:
            positions = jnp.broadcast_to(cur, (b, 1, 3)).astype(jnp.int32)
        else:
            positions = jnp.broadcast_to(cur, (b, 1)).astype(jnp.int32)
        cos, sin = _rope_for(cfg, positions)
        x = params["embed"][tokens][:, None].astype(compute_dtype)

        if n_pre:
            x, new_cache = _decode_split(params, x, cache, cos, sin)
        else:
            x, new_cache = scan_layers_with_cache(
                lambda x, lp, lc: layer_decode(lp, x, lc, cos, sin, cfg,
                                               cfg.uses_moe, rolling_decode),
                x, params["layers"], cache)
        h = rmsnorm(params["final_norm"], x[:, 0:1], cfg.norm_eps)
        logits = _unembed(params, cfg, h[:, 0])
        return logits, new_cache

    def _decode_split(params, x, cache, cos, sin):
        """first_k_dense archs: split the cache between the two stacks."""
        pre_cache = jax.tree.map(lambda a: a[:n_pre], cache)
        main_cache = jax.tree.map(lambda a: a[n_pre:], cache)
        x, new_pre = scan_layers_with_cache(
            lambda x, lp, lc: layer_decode(lp, x, lc, cos, sin, cfg, False,
                                           rolling_decode),
            x, params["layers_dense"], pre_cache)
        x, new_main = scan_layers_with_cache(
            lambda x, lp, lc: layer_decode(lp, x, lc, cos, sin, cfg,
                                           cfg.uses_moe, rolling_decode),
            x, params["layers"], main_cache)
        new_cache = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0),
                                 new_pre, new_main)
        return x, new_cache

    # ---------------------- paged serving ---------------------------- #
    # Pages are stacked on the layer dim like the dense cache and
    # threaded through the same layer scan; the block table and per-slot
    # lengths stay OUTSIDE the per-layer pytree (one copy, closed over by
    # the scan bodies) because every layer shares them.

    def init_paged_cache(n_pages: int, page_size: int):
        def one(_):
            if cfg.kv_lora_rank:
                return init_paged_mla(n_pages, page_size, cfg.kv_lora_rank,
                                      cfg.qk_rope_head_dim, cache_dtype)
            return init_paged_kv(n_pages, page_size, cfg.n_kv_heads,
                                 cfg.resolved_head_dim, cache_dtype)
        return jax.tree.map(lambda *xs: jnp.stack(xs),
                            *[one(i) for i in range(cfg.n_layers)])

    def _scan_paged(params, x, pages, body_for):
        """Run the (dense-prefix +) main stacks over stacked pages."""
        if not n_pre:
            return scan_layers_with_cache(body_for(cfg.uses_moe), x,
                                          params["layers"], pages)
        pre = jax.tree.map(lambda a: a[:n_pre], pages)
        main = jax.tree.map(lambda a: a[n_pre:], pages)
        x, new_pre = scan_layers_with_cache(body_for(False), x,
                                            params["layers_dense"], pre)
        x, new_main = scan_layers_with_cache(body_for(cfg.uses_moe), x,
                                             params["layers"], main)
        return x, jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0),
                               new_pre, new_main)

    def _positions_for(pos):
        """pos [B,S] int32 -> rope positions ([B,S] or [B,S,3] M-RoPE)."""
        if cfg.mrope:
            return jnp.stack([pos, pos, pos], axis=-1)
        return pos

    def prefill_paged_chunk(params, tokens, pages, block_tables, base):
        """One prompt chunk: tokens [B,C] at global positions
        base..base+C-1 (base is traced — any chunk index reuses the one
        compiled program).  Returns (logits [B,C,V], pages)."""
        b, c = tokens.shape
        pos = base + jnp.broadcast_to(jnp.arange(c), (b, c))
        cos, sin = _rope_for(cfg, _positions_for(pos.astype(jnp.int32)))
        x = params["embed"][tokens].astype(compute_dtype)

        def body_for(use_moe):
            def body(x, lp, lpg):
                return layer_prefill_paged(lp, x, lpg, block_tables, base,
                                           cos, sin, cfg, use_moe)
            return body

        x, new_pages = _scan_paged(params, x, pages, body_for)
        h = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return _unembed(params, cfg, h), new_pages

    def decode_step_paged(params, tokens, pages, block_tables, lengths,
                          active):
        """One decode step over the slot array: tokens [B], per-slot
        ``lengths`` [B] (cached tokens so far — the position each slot's
        token is written at), ``active`` [B] bool.  Returns
        (logits [B,V], pages)."""
        b = tokens.shape[0]
        pos = lengths.astype(jnp.int32)[:, None]          # [B,1] per slot
        cos, sin = _rope_for(cfg, _positions_for(pos))
        x = params["embed"][tokens][:, None].astype(compute_dtype)

        def body_for(use_moe):
            def body(x, lp, lpg):
                return layer_decode_paged(lp, x, lpg, block_tables,
                                          lengths, active, cos, sin, cfg,
                                          use_moe, decode_impl)
            return body

        x, new_pages = _scan_paged(params, x, pages, body_for)
        h = rmsnorm(params["final_norm"], x[:, 0:1], cfg.norm_eps)
        return _unembed(params, cfg, h[:, 0]), new_pages

    return ModelBundle(cfg=cfg, init=init, loss_fn=loss_fn, prefill=prefill,
                       decode_step=decode_step, init_cache=init_cache,
                       forward=forward, init_paged_cache=init_paged_cache,
                       prefill_paged_chunk=prefill_paged_chunk,
                       decode_step_paged=decode_step_paged)


# ===================================================================== #
# RWKV-6 LM
# ===================================================================== #

def build_rwkv_lm(cfg: ArchConfig, *, param_dtype=jnp.float32,
                  compute_dtype=None, remat: bool = False,
                  impl: str = "auto", **_unused) -> ModelBundle:
    compute_dtype = compute_dtype or param_dtype
    H, hd = cfg.ssm_heads, cfg.resolved_head_dim

    def init(key):
        ks = jax.random.split(key, 3)
        return {
            "embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model,
                                param_dtype),
            "layers": stacked_init(
                lambda k: rwk.block_init(k, cfg.d_model, cfg.d_ff, H, hd,
                                         param_dtype), ks[1], cfg.n_layers),
            "final_norm": rmsnorm_init(cfg.d_model, param_dtype),
            "lm_head": dense_init(ks[2], cfg.d_model, cfg.padded_vocab,
                                  param_dtype),
        }

    def forward(params, embeds, positions=None):
        def body(x, lp):
            return rwk.block_apply(lp, x, n_heads=H, head_dim=hd,
                                   eps=cfg.norm_eps, impl=impl)
        fn = jax.checkpoint(body) if remat else body

        def step(c, lp):
            return fn(c, lp), None
        x, _ = jax.lax.scan(step, embeds.astype(compute_dtype),
                            params["layers"])
        return rmsnorm(params["final_norm"], x, cfg.norm_eps), \
            jnp.zeros((), jnp.float32)

    def loss_fn(params, batch):
        h, _ = forward(params, params["embed"][batch["tokens"]])
        with jax.named_scope(HEAD_LOSS):
            return softmax_cross_entropy(h @ params["lm_head"],
                                         batch["labels"], batch.get("mask"))

    def init_cache(batch: int, max_len: int = 0):
        states = [rwk.init_block_state(batch, cfg.d_model, H, hd)
                  for _ in range(cfg.n_layers)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *states)

    def prefill(params, batch):
        """Run the recurrence across the prompt, keep final states."""
        x = params["embed"][batch["tokens"]].astype(compute_dtype)
        b = x.shape[0]

        def body(x, lp, st):
            h_in = rmsnorm(lp["ln1"], x, cfg.norm_eps)
            h, tm_shift, wkv = rwk.timemix_apply(
                lp["tm"], h_in, n_heads=H, head_dim=hd, eps=cfg.norm_eps,
                shift_state=None, wkv_state=st["wkv"], impl=impl)
            x = x + h
            h2_in = rmsnorm(lp["ln2"], x, cfg.norm_eps)
            h2, cm_shift = rwk.channelmix_apply(lp["cm"], h2_in)
            new_st = {"tm_shift": tm_shift, "wkv": wkv,
                      "cm_shift": cm_shift}
            return x + h2, new_st

        cache = init_cache(b)
        x, new_cache = scan_layers_with_cache(body, x, params["layers"],
                                              cache)
        h = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return h[:, -1] @ params["lm_head"], new_cache

    def decode_step(params, tokens, cache):
        x = params["embed"][tokens][:, None].astype(compute_dtype)

        def body(x, lp, st):
            return rwk.block_decode(lp, x, st, n_heads=H, head_dim=hd,
                                    eps=cfg.norm_eps)
        x, new_cache = scan_layers_with_cache(body, x, params["layers"],
                                              cache)
        h = rmsnorm(params["final_norm"], x[:, 0], cfg.norm_eps)
        return h @ params["lm_head"], new_cache

    return ModelBundle(cfg=cfg, init=init, loss_fn=loss_fn, prefill=prefill,
                       decode_step=decode_step, init_cache=init_cache,
                       forward=forward)


# ===================================================================== #
# Hymba hybrid LM
# ===================================================================== #

def build_hymba_lm(cfg: ArchConfig, *, param_dtype=jnp.float32,
                   compute_dtype=None, remat: bool = False,
                   impl: str = "auto", cache_dtype=jnp.bfloat16,
                   **_unused) -> ModelBundle:
    compute_dtype = compute_dtype or param_dtype
    kw = dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
              head_dim=cfg.resolved_head_dim, ssm_state=cfg.ssm_state,
              eps=cfg.norm_eps, act=cfg.act)

    def init(key):
        ks = jax.random.split(key, 3)
        return {
            "embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model,
                                param_dtype),
            "layers": stacked_init(
                lambda k: hyb.hymba_block_init(
                    k, d_model=cfg.d_model, n_heads=cfg.n_heads,
                    n_kv_heads=cfg.n_kv_heads,
                    head_dim=cfg.resolved_head_dim, d_ff=cfg.d_ff,
                    ssm_state=cfg.ssm_state, ssm_expand=cfg.ssm_expand,
                    act=cfg.act, dtype=param_dtype), ks[1], cfg.n_layers),
            "final_norm": rmsnorm_init(cfg.d_model, param_dtype),
            "lm_head": dense_init(ks[2], cfg.d_model, cfg.padded_vocab,
                                  param_dtype),
        }

    def forward(params, embeds, positions=None):
        b, s, _ = embeds.shape
        pos = text_positions(b, s) if positions is None else positions
        cos, sin = rope_cos_sin(pos, cfg.resolved_head_dim, cfg.rope_theta)

        def body(x, lp):
            return hyb.hymba_block_apply(lp, x, cos, sin,
                                         window=cfg.sliding_window,
                                         impl=impl, **kw)
        fn = jax.checkpoint(body) if remat else body

        def step(c, lp):
            return fn(c, lp), None
        x, _ = jax.lax.scan(step, embeds.astype(compute_dtype),
                            params["layers"])
        return rmsnorm(params["final_norm"], x, cfg.norm_eps), \
            jnp.zeros((), jnp.float32)

    def loss_fn(params, batch):
        h, _ = forward(params, params["embed"][batch["tokens"]])
        with jax.named_scope(HEAD_LOSS):
            return softmax_cross_entropy(h @ params["lm_head"],
                                         batch["labels"], batch.get("mask"))

    def init_cache(batch: int, max_len: int = 0):
        states = [hyb.init_hymba_state(
            batch, d_model=cfg.d_model, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.resolved_head_dim, ssm_state=cfg.ssm_state,
            ssm_expand=cfg.ssm_expand, window=cfg.sliding_window,
            dtype=cache_dtype) for _ in range(cfg.n_layers)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *states)

    def prefill(params, batch):
        x = params["embed"][batch["tokens"]].astype(compute_dtype)
        b, s, _ = x.shape
        pos = text_positions(b, s)
        cos, sin = rope_cos_sin(pos, cfg.resolved_head_dim, cfg.rope_theta)
        cache = init_cache(b)

        def body(x, lp, st):
            h = rmsnorm(lp["ln_in"], x, cfg.norm_eps)
            from repro.models.attention import (gqa_attention as _ga,
                                                prefill_kv_cache as _pf)
            a = _ga(lp["attn"], h, cos, sin, n_heads=cfg.n_heads,
                    n_kv_heads=cfg.n_kv_heads,
                    head_dim=cfg.resolved_head_dim,
                    window=cfg.sliding_window, impl=impl)
            kv = _pf(lp["attn"], h, cos, sin, n_heads=cfg.n_heads,
                     n_kv_heads=cfg.n_kv_heads,
                     head_dim=cfg.resolved_head_dim,
                     max_len=cfg.sliding_window, dtype=cache_dtype,
                     rolling=True, window=cfg.sliding_window)
            from repro.models import mamba as mam
            m, hT, conv_tail = mam.mamba_apply(lp["ssm"], h,
                                               state=cfg.ssm_state)
            fused = 0.5 * (rmsnorm(lp["ln_attn"], a, cfg.norm_eps)
                           + rmsnorm(lp["ln_ssm"], m, cfg.norm_eps))
            x = x + fused
            x = x + mlp_apply(lp["mlp"],
                              rmsnorm(lp["ln_mlp"], x, cfg.norm_eps), cfg.act)
            return x, {"kv": kv, "ssm": hT, "conv": conv_tail}

        x, new_cache = scan_layers_with_cache(body, x, params["layers"],
                                              cache)
        h = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return h[:, -1] @ params["lm_head"], new_cache

    def decode_step(params, tokens, cache):
        b = tokens.shape[0]
        cur = cache["kv"]["pos"][0]
        pos = jnp.broadcast_to(cur, (b, 1)).astype(jnp.int32)
        cos, sin = rope_cos_sin(pos, cfg.resolved_head_dim, cfg.rope_theta)
        x = params["embed"][tokens][:, None].astype(compute_dtype)

        def body(x, lp, st):
            return hyb.hymba_block_decode(lp, x, st, cos, sin, **kw)
        x, new_cache = scan_layers_with_cache(body, x, params["layers"],
                                              cache)
        h = rmsnorm(params["final_norm"], x[:, 0], cfg.norm_eps)
        return h @ params["lm_head"], new_cache

    return ModelBundle(cfg=cfg, init=init, loss_fn=loss_fn, prefill=prefill,
                       decode_step=decode_step, init_cache=init_cache,
                       forward=forward)
