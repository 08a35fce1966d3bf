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
