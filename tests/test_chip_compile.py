"""The main path's Pallas kernels, compiled for a TPU v5e that is described
but not attached, at the shapes the system runs them.

Interpret mode (tests/test_kernels.py) checks what a kernel computes; only
the TPU compiler says whether Mosaic accepts its tiling, its VMEM
residency and its stores.  Each compile takes a second or two and runs
no kernel.  The topology is described inside a fixture, never at import:
only one process may load the TPU library, and it keeps it until exit.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

BUCKET_ROW = (4 << 20) // 4     # f32 elements in a default 4 MiB bucket
LEARNERS = 4                    # HierTopology(1, 2, 2): rows of a bucket


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compile_for(sharding, fn, *specs):
    """Compile ``fn`` for the described chip; returns the executable."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "the kernel did not lower to Mosaic"
    return compiled


def test_flash_decode_compiles_at_qwen2_vl_2b_serve_shapes(one_chip):
    """One paged decode step's attention as chip_smoke.py serves
    qwen2-vl-2b: 4 slots of 80 tokens (prompt 64 + 16 new, padded to the
    32-token prefill chunk) in 16-token bf16 pages; f32 queries."""
    from repro.configs import get_config
    from repro.serve.kvcache import pages_for, pool_pages
    cfg = get_config("qwen2-vl-2b")
    slots, page, padded_len = 4, 16, 96
    per_seq = pages_for(padded_len, page)
    n_pages = pool_pages(cfg, page, slots=slots, max_len=padded_len)
    d = cfg.resolved_head_dim
    compile_for(
        one_chip,
        lambda q, k, v, t, n: ops.flash_decode(q, k, v, t, n,
                                               impl="pallas"),
        ((slots, cfg.n_heads, d), jnp.float32),
        ((cfg.n_kv_heads, n_pages, page, d), jnp.bfloat16),
        ((cfg.n_kv_heads, n_pages, page, d), jnp.bfloat16),
        ((slots, per_seq), jnp.int32),
        ((slots,), jnp.int32))


# the cells' attention layers: (heads, kv heads, head dim, window) of
# hymba-1.5b (a 1024 window) and qwen2-vl-2b (causal) over 4096 positions
@pytest.mark.parametrize("hq,hkv,d,window", [(25, 5, 64, 1024),
                                             (12, 2, 128, 0)],
                         ids=["hymba-1.5b", "qwen2-vl-2b"])
def test_fused_attention_grad_compiles_at_cell_shapes(one_chip, hq, hkv, d,
                                                      window):
    """The gradient of one GQA layer through the fused kernel, as a
    training step takes it: forward, dq and dk/dv kernels, each billed
    to the ``attention`` scope by its ``op_name``."""
    from repro.models.attention import gqa_attention, gqa_init
    d_model = 1536
    p = jax.eval_shape(lambda: gqa_init(jax.random.PRNGKey(0), d_model, hq,
                                        hkv, d))

    def loss(p, x):
        return gqa_attention(p, x, None, None, n_heads=hq, n_kv_heads=hkv,
                             head_dim=d, window=window,
                             impl="pallas").sum()

    specs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (p, jax.ShapeDtypeStruct((1, 4096, d_model), jnp.float32)))
    compiled = jax.jit(jax.grad(loss)).lower(*specs).compile()
    names = [re.search(r'op_name="([^"]*)"', ln).group(1)
             for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert sorted(n.rsplit("/", 2)[1] for n in names) == [
        "flash_attention_dkv", "flash_attention_dq", "flash_attention_fwd"]
    assert all(re.search(r"(^|[/(])attention[/)]", n) for n in names), names


def _gqa_archs():
    """Each architecture of the zoo whose self-attention is GQA through
    ``models/attention.full_attention`` (MLA, RWKV and the CNN are not),
    by (heads, kv heads, head dim, window)."""
    from repro.configs import get_config, list_archs
    shapes = {}
    for arch in list_archs():
        c = get_config(arch)
        if c.n_kv_heads and not c.kv_lora_rank:
            shapes[arch] = (c.n_heads, c.n_kv_heads, c.resolved_head_dim,
                            c.sliding_window)
    return shapes


GQA_ARCHS = _gqa_archs()


@pytest.mark.parametrize("arch", sorted(GQA_ARCHS))
def test_fused_attention_grad_compiles_for_every_gqa_arch(one_chip, arch):
    """The dispatch takes the fused kernel at every GQA shape of the zoo
    over 4096 positions, at the block whose VMEM ``blocks`` finds to fit,
    and the compiler takes all three kernels within ``VMEM_LIMIT``."""
    from repro.kernels import flash_attention as fa
    from repro.models import attention as A
    hq, hkv, d, window = GQA_ARCHS[arch]

    def loss(q, k, v):
        return A.full_attention(q, k, v, window=window, impl="pallas").sum()

    specs = [jax.ShapeDtypeStruct((1, 4096, h, d), jnp.float32,
                                  sharding=one_chip) for h in (hq, hkv, hkv)]
    with A.DispatchRecord() as rec:
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))) \
            .lower(*specs).compile()
    assert [c.path for c in rec.calls] == ["fused"], rec.describe()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') \
        == 3
    assert fa.vmem_bytes(hq // hkv, d, fa.blocks(4096, window, hq // hkv, d)) \
        <= fa.VMEM_LIMIT


# (query heads a kv head, head dim, block): the cells' shapes and the
# zoo's larger groups at two block sizes
@pytest.mark.parametrize("g,d,b", [(5, 64, 512), (6, 128, 512),
                                   (8, 128, 512), (12, 128, 256),
                                   (12, 128, 512)])
def test_fused_attention_fits_the_vmem_it_is_given(one_chip, monkeypatch,
                                                   g, d, b):
    """``vmem_bytes`` is at or above what the compiler needs: all three
    kernels compile with their scoped VMEM limited to its estimate."""
    from repro.kernels import flash_attention as fa
    monkeypatch.setattr(fa, "VMEM_LIMIT", fa.vmem_bytes(g, d, b))

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, block_q=b, block_k=b).sum()

    compiled = compile_for(one_chip, jax.grad(loss, argnums=(0, 1, 2)),
                           ((1, 4096, g, d), jnp.float32),
                           ((1, 4096, 1, d), jnp.float32),
                           ((1, 4096, 1, d), jnp.float32))
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') \
        == 3


def test_qint8_pack_compiles_at_bucket_row(one_chip):
    compile_for(one_chip,
                lambda x: ops.qint8_pack(x, 128, impl="pallas"),
                ((LEARNERS, BUCKET_ROW), jnp.float32))


def test_qint8_unpack_compiles_at_bucket_row(one_chip):
    compile_for(one_chip,
                lambda w: ops.qint8_unpack(w, BUCKET_ROW, impl="pallas"),
                ((LEARNERS, BUCKET_ROW // 128, 128 + 4), jnp.int8))


def test_qint8_codec_stays_small_for_stacked_learners(one_chip):
    """The codec round trip and group mean on four learners stacked on
    one chip, ``[1, 2, 2, n]`` (HierTopology(1, 2, 2)), at hymba-1.5b's
    d_model x d_ff leaf.  Merging the learner axes into rows by a
    reshape made the compiler emit code in proportion to ``n`` (about
    50 MB and a minute for this one leaf); rows taken by index compile
    to a few MB in a second."""
    n = 1600 * 5504

    def round_trip(x):
        wire = ops.qint8_pack(x, 128, impl="pallas")
        return ops.qint8_unpack(wire, n, impl="pallas").mean(axis=2)

    compiled = compile_for(one_chip, round_trip,
                           ((1, 2, 2, n), jnp.float32))
    code = compiled.memory_analysis().generated_code_size_in_bytes
    assert code < 8 << 20, f"{code} bytes of code"


# the rank-2 PowerSGD panel heights of hymba-1.5b's tree at its published
# depth (chip_smoke.panel_shapes): the 32 stacked per-layer vectors,
# d_model, and the vocabulary padded to the lane multiple
@pytest.mark.parametrize("a", [32, 1600, 32128])
def test_batched_qr_compiles_at_hymba_panels(one_chip, a):
    compile_for(one_chip, lambda p: ops.batched_qr(p, impl="pallas"),
                ((LEARNERS, a, 2), jnp.float32))


@pytest.mark.parametrize("compaction,k", [
    ("scan", BUCKET_ROW // 100),     # topk:0.01 — k past block_n
    ("onehot", 512),                 # the legacy engine's k < block_n side
])
def test_topk_compress_compiles_at_bucket_row(one_chip, compaction, k):
    compile_for(one_chip,
                lambda x: ops.topk_compress(x, k, impl="pallas",
                                            compaction=compaction),
                ((LEARNERS, BUCKET_ROW), jnp.float32))
