"""Blockwise online-softmax (flash) attention as a Pallas TPU kernel.

TPU-native design (not a CUDA port):
  * Block shapes are multiples of the (8, 128) VREG tile and the q/k blocks
    feed the 128x128 MXU: block_q/block_k default 128.
  * Grid = (batch*heads, q_blocks, kv_blocks) with the kv dimension iterated
    sequentially ("arbitrary") so the running (m, l, acc) softmax state lives
    in VMEM scratch across kv steps — the HBM->VMEM streaming schedule is
    expressed entirely through BlockSpec index maps.
  * GQA is expressed in the index map: the kv BlockSpec maps query-head
    index h -> kv-head h // group, so K/V are streamed once per kv head
    without materializing the head-repeated tensors in HBM.
  * Causal + sliding-window masks are applied inside the kernel with
    block-level iota; fully-masked kv blocks short-circuit via pl.when.

Validated against kernels/ref.py::flash_attention_ref with interpret=True
(CPU) across shape/dtype sweeps (tests/test_kernels.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1.0e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, window: int, block_q: int,
                 block_k: int, kv_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # skip kv blocks that are entirely in the future (causal) or entirely
    # fallen out of the sliding window
    run = jnp.bool_(True)
    if causal:
        run &= k_start <= q_start + block_q - 1
    if window:
        # newest query in this block is q_start+block_q-1; the oldest key it
        # can see is q_start - (window - 1)
        run &= k_start + block_k - 1 >= q_start - (window - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)               # [bq, d]
        k = k_ref[0].astype(jnp.float32)               # [bk, d]
        v = v_ref[0].astype(jnp.float32)               # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]

        if causal or window:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            mask = jnp.ones((block_q, block_k), jnp.bool_)
            if causal:
                mask &= kpos <= qpos
            if window:
                mask &= (qpos - kpos) < window
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                            # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    scale: Optional[float] = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False) -> jax.Array:
    """q [B,S,Hq,D]; k/v [B,T,Hkv,D] -> [B,S,Hq,D].

    S must be divisible by block_q and T by block_k (callers pad; the sweep
    tests cover the aligned shapes the models produce).
    """
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    if scale is None:
        scale = 1.0 / float(d) ** 0.5

    # [B, S, H, D] -> [B*H, S, D] so the grid's first axis is batch*heads
    qr = q.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * hkv, t, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * hkv, t, d)

    q_blocks = s // block_q
    kv_blocks = t // block_k
    grid = (b * hq, q_blocks, kv_blocks)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        # query head bh = bi*hq + h attends kv head h // group
        bi = bh // hq
        h = bh % hq
        return (bi * hkv + h // group, ki, 0)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, kv_blocks=kv_blocks)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        name="flash_attention",
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b * hq, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)

    return out.reshape(b, hq, s, d).transpose(0, 2, 1, 3)
