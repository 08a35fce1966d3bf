"""Pallas kernel validation: shape/dtype sweeps, interpret=True vs the
pure-jnp oracles in kernels/ref.py."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import (FIRST, LAST, MASKED,
                                           flash_attention, tiles)

ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _tol(dtype):
    return ATOL[jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32]


# the larger interpret-mode sweep shapes are slow-tier; scripts/test_fast.sh
# still runs the full kernel suite explicitly (pytest -m "" tests/test_kernels.py)
_slow = pytest.mark.slow


# The fused attention kernel rounds each matmul's operands to bfloat16
# once (the TPU's default precision for float32): against the float32
# oracle it is held to the bfloat16 tolerance whatever the input dtype,
# and against the same arithmetic with that rounding (_contract_attention)
# to CONTRACT_TOL, the norm of the difference over the norm of the
# oracle's value.  Only the accumulation order differs there, which moves
# a rare bfloat16 rounding of a probability (readings 2e-5 to 4.4e-5 at
# 512 positions); a statistic, accumulator, log-sum-exp or output kept
# in bfloat16, or a probability rounded twice, reads 1e-3 and more.
ATTN_TOL = ATOL[jnp.bfloat16]
CONTRACT_TOL = 1e-4
NEG_INF = -0.7 * float(np.finfo(np.float32).max)    # the kernel's


def _qkv(seed, b, s, hq, hkv, d, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, s, hq, d), dtype),
            jax.random.normal(ks[1], (b, s, hkv, d), dtype),
            jax.random.normal(ks[2], (b, s, hkv, d), dtype))


def _close(got, want, tol=ATTN_TOL):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _contract_attention(q, k, v, do=None, *, window=0, block_k):
    """Causal (windowed) GQA attention and its (q, k, v) gradients for a
    cotangent ``do``, by the kernel's numerics contract written plainly
    over whole arrays: q, k, v, do, each probability tile and ds rounded
    to bfloat16 once before their matmul, everything else float32.  The
    forward is the online softmax over key tiles of ``block_k``, each
    tile's probabilities rounded against the running max; the backward
    recomputes them from the log-sum-exp.  A tile hidden from a row adds
    exactly nothing, so no tile list is needed."""
    hi = jax.lax.Precision.HIGHEST
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g, scale = hq // hkv, 1.0 / d ** 0.5
    qb = _bf16(q).reshape(b, s, hkv, g, d)
    kb, vb = _bf16(k), _bf16(v)
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    vis = (j <= i) & ((i - j < window) if window else True)
    st = jnp.where(vis, jnp.einsum("bskgd,btkd->bkgst", qb, kb,
                                   precision=hi) * scale, NEG_INF)
    m = jnp.full(st.shape[:-1] + (1,), NEG_INF)
    l, acc = jnp.zeros_like(m), 0.0
    for t in range(0, s, block_k):
        tile = st[..., t:t + block_k]
        m_next = jnp.maximum(m, tile.max(axis=-1, keepdims=True))
        p, alpha = jnp.exp(tile - m_next), jnp.exp(m - m_next)
        l = alpha * l + p.sum(axis=-1, keepdims=True)
        acc = alpha * acc + jnp.einsum("bkgst,btkd->bkgsd", _bf16(p),
                                       vb[:, t:t + block_k], precision=hi)
        m = m_next
    o = acc / l
    out = {"o": o.transpose(0, 3, 1, 2, 4).reshape(q.shape)}
    if do is None:
        return out
    p = jnp.exp(st - (m + jnp.log(l)))
    dob = _bf16(do).reshape(b, s, hkv, g, d)
    di = jnp.einsum("bskgd,bkgsd->bkgs", do.reshape(b, s, hkv, g, d), o,
                    precision=hi)[..., None]
    ds = _bf16(p * (jnp.einsum("bskgd,btkd->bkgst", dob, vb, precision=hi)
                    - di))
    out["dq"] = (jnp.einsum("bkgst,btkd->bskgd", ds, kb, precision=hi)
                 * scale).reshape(q.shape)
    out["dk"] = jnp.einsum("bkgst,bskgd->btkd", ds, qb, precision=hi) * scale
    out["dv"] = jnp.einsum("bkgst,bskgd->btkd", _bf16(p), dob, precision=hi)
    return out


def _contract_gap(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("b,s,hq,hkv,d", [
    (1, 128, 1, 1, 64),
    pytest.param(2, 256, 4, 2, 64, marks=_slow),
    pytest.param(1, 256, 8, 8, 128, marks=_slow),
    (2, 128, 6, 2, 32),
    pytest.param(1, 512, 4, 1, 64, marks=_slow),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(b, s, hq, hkv, d, dtype):
    q, k, v = _qkv(0, b, s, hq, hkv, d, dtype)
    o_ref = ref.flash_attention_ref(q, k, v, causal=True)
    o = flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)
    assert o.dtype == dtype
    _close(o, o_ref)
    want = _contract_attention(q, k, v, block_k=128)["o"].astype(dtype)
    assert _contract_gap(o, want) < CONTRACT_TOL


@pytest.mark.parametrize("window", [32, 100, 256])
def test_flash_attention_window(window):
    """64-position blocks over 256: the window hides whole key tiles."""
    q, k, v = _qkv(1, 2, 256, 4, 2, 64)
    o_ref = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    o = flash_attention(q, k, v, window=window, block_q=64, block_k=64,
                        interpret=True)
    _close(o, o_ref)
    want = _contract_attention(q, k, v, window=window, block_k=64)["o"]
    assert _contract_gap(o, want) < CONTRACT_TOL


@pytest.mark.parametrize("block_q,block_k", [(64, 64), (128, 64), (64, 128)])
def test_flash_attention_blocks(block_q, block_k):
    q, k, v = _qkv(2, 1, 256, 2, 2, 64)
    o_ref = ref.flash_attention_ref(q, k, v, causal=True)
    o = flash_attention(q, k, v, block_q=block_q, block_k=block_k,
                        interpret=True)
    _close(o, o_ref)
    want = _contract_attention(q, k, v, block_k=block_k)["o"]
    assert _contract_gap(o, want) < CONTRACT_TOL


def test_flash_attention_tiles_skip_hidden_blocks():
    """The tile list holds exactly the tiles with a visible key, each
    flagged for masking iff it straddles the mask's edge, in rows."""
    s, b, window = 1024, 128, 300
    q = np.arange(s)[:, None]
    key = np.arange(s)[None, :]
    vis = (key <= q) & (q - key < window)
    for by_key in (False, True):
        rows, cols, flags = tiles(s, b, b, window, by_key=by_key)
        got = set()
        for r, c, f in zip(rows, cols, flags):
            i, j = (c, r) if by_key else (r, c)
            blk = vis[i * b:(i + 1) * b, j * b:(j + 1) * b]
            assert blk.any()
            assert bool(f & MASKED) == (not blk.all())
            got.add((i, j))
        want = {(i, j) for i in range(s // b) for j in range(s // b)
                if vis[i * b:(i + 1) * b, j * b:(j + 1) * b].any()}
        assert got == want
        assert np.all(np.diff(rows) >= 0)
        starts = np.r_[True, rows[1:] != rows[:-1]]
        ends = np.r_[rows[1:] != rows[:-1], True]
        np.testing.assert_array_equal((flags & FIRST) != 0, starts)
        np.testing.assert_array_equal((flags & LAST) != 0, ends)


# the cells' attention at a small sequence: 128-position blocks over 512
# positions; a window that is not a multiple of the block
FUSED_CASES = {
    "causal-g6-d128": dict(b=1, s=512, hq=12, hkv=2, d=128, window=0),
    "window-g5-d64": dict(b=1, s=512, hq=10, hkv=2, d=64, window=200),
}


@functools.lru_cache(maxsize=None)
def _fused_vs_references(case):
    """Outputs and (q, k, v) gradients of the fused kernel, of the float32
    oracle, of the XLA path the models ran before (query chunks) and of
    the rounding contract."""
    from repro.models.attention import _chunked_causal_attend
    c = FUSED_CASES[case]
    q, k, v = _qkv(3, c["b"], c["s"], c["hq"], c["hkv"], c["d"])
    do = jax.random.normal(jax.random.PRNGKey(4), q.shape)
    w, scale = c["window"], 1.0 / c["d"] ** 0.5
    impls = {
        "fused": lambda q, k, v: flash_attention(
            q, k, v, window=w, block_q=128, block_k=128, interpret=True),
        "ref": lambda q, k, v: ref.flash_attention_ref(
            q, k, v, causal=True, window=w),
        "xla": lambda q, k, v: _chunked_causal_attend(
            q, k, v, window=w, scale=scale, q_chunk=128),
    }
    out = {}
    for name, fn in impls.items():
        o, vjp = jax.vjp(fn, q, k, v)
        out[name] = dict(zip(("o", "dq", "dk", "dv"), (o, *vjp(do))))
    out["contract"] = _contract_attention(q, k, v, do, window=w,
                                          block_k=128)
    return out


@pytest.mark.parametrize("quantity", ["o", "dq", "dk", "dv"])
@pytest.mark.parametrize("case", sorted(FUSED_CASES))
def test_fused_attention_matches_oracle_and_xla(case, quantity):
    res = _fused_vs_references(case)
    got = np.asarray(res["fused"][quantity])
    assert got.dtype == np.float32
    for other in ("ref", "xla"):
        want = np.asarray(res[other][quantity])
        err = np.abs(got - want).max() / np.abs(want).max()
        assert err < ATTN_TOL, (other, err)


@pytest.mark.parametrize("quantity", ["o", "dq", "dk", "dv"])
@pytest.mark.parametrize("case", sorted(FUSED_CASES))
def test_fused_attention_follows_the_rounding_contract(case, quantity):
    res = _fused_vs_references(case)
    gap = _contract_gap(res["fused"][quantity], res["contract"][quantity])
    assert gap < CONTRACT_TOL, gap


@pytest.mark.parametrize("compaction", ["scan", "onehot"])
@pytest.mark.parametrize("rows,n,k,block_n", [
    (1, 64, 1, 64),
    (5, 300, 30, 128),      # n not a block multiple -> padded tail
    (3, 1024, 102, 256),
    (2, 128, 128, 64),      # k == n (everything transmitted)
    (4, 17, 3, 1024),       # block_n > n
])
def test_topk_compress_interpret_matches_ref(rows, n, k, block_n,
                                             compaction):
    """Fused threshold+compaction kernel == lax.top_k oracle (fp32 inputs
    have no magnitude ties, so the selections agree exactly) — for both
    the scalable carried-offset compaction and the legacy one-hot."""
    x = jax.random.normal(jax.random.PRNGKey(n + k), (rows, n))
    v_ref, i_ref = ref.topk_compress_ref(x, k)
    v, i = ops.topk_compress(x, k, impl="pallas_interpret", block_n=block_n,
                             compaction=compaction)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
    np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref), rtol=1e-6)


def test_topk_compress_bf16_magnitudes():
    """bf16 rounds values onto a coarse grid, so magnitude ties at the
    threshold are legal tie-breaks — the *selected magnitudes* must still
    match the oracle even when the tied indices differ."""
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 256), jnp.bfloat16)
    v_ref, _ = ref.topk_compress_ref(x, 25)
    v, i = ops.topk_compress(x, 25, impl="pallas_interpret")
    assert i.dtype == jnp.int32 and v.dtype == x.dtype
    a = np.sort(np.abs(np.asarray(v, np.float32)), axis=-1)
    b = np.sort(np.abs(np.asarray(v_ref, np.float32)), axis=-1)
    np.testing.assert_allclose(a, b, rtol=1e-6)


def test_topk_compress_heavy_tailed_magnitudes():
    """Scale-free threshold search: a 1e8 outlier next to ~1.0 values must
    not cost selection precision (regression: value-domain bisection lost
    ~23 bits here and kept wrong elements)."""
    x = 0.9 + 0.1 * jax.random.uniform(jax.random.PRNGKey(11), (1, 8193))
    x = x.at[0, 4000].set(1e8)
    v_ref, i_ref = ref.topk_compress_ref(x, 100)
    v, i = ops.topk_compress(x, 100, impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))


def test_topk_compress_ties_and_zeros():
    """Exact tie handling: tied magnitudes at the threshold break to the
    lowest indices (lax.top_k's stable order) and zero rows are legal."""
    x = jnp.zeros((2, 64)).at[0, 5].set(0.5).at[0, 9].set(0.5) \
        .at[0, 40].set(-0.5).at[1, 60].set(-2.0)
    v_ref, i_ref = ref.topk_compress_ref(x, 2)
    v, i = ops.topk_compress(x, 2, impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))


def test_topk_compress_indices_sorted_and_exact_k():
    x = jax.random.normal(jax.random.PRNGKey(8), (4, 500))
    for impl in ("xla", "pallas_interpret"):
        v, i = ops.topk_compress(x, 50, impl=impl)
        i = np.asarray(i)
        assert (np.diff(i, axis=-1) > 0).all()        # strictly ascending
        assert v.shape == (4, 50) and i.shape == (4, 50)


def test_topk_compress_row_cap_gated_on_legacy_compaction():
    """The 2^24 flat-row cap belongs to the legacy one-hot compaction
    (fp32 index accumulation); the scan compaction keeps exact int32
    indices and must trace past it.  The error names the offending
    shape."""
    big = jax.ShapeDtypeStruct((2, 2 ** 24 + 64), jnp.float32)
    with pytest.raises(ValueError, match=r"\(2, 16777280\)"):
        jax.eval_shape(lambda x: ops.topk_compress(
            x, 8, impl="pallas", compaction="onehot"), big)
    # explicit scan AND the default auto dispatch trace past the cap
    for compaction in ("scan", "auto"):
        v, i = jax.eval_shape(lambda x, c=compaction: ops.topk_compress(
            x, 8, impl="pallas", compaction=c), big)
        assert v.shape == (2, 8) and i.shape == (2, 8)
        assert i.dtype == jnp.int32


@pytest.mark.slow
def test_topk_compress_scan_row_beyond_2e24_interpret():
    """The scalable compaction's whole point: a >2^24-element row with
    outliers planted ABOVE 2^24 keeps exact indices (the legacy engine's
    fp32 accumulation cannot represent them).  ~3 min in interpret mode
    on 2 CPU cores — slow tier; scripts/test_fast.sh deselects it."""
    n = 2 ** 24 + 4096
    x = jax.random.normal(jax.random.PRNGKey(0), (1, n), jnp.float32)
    # plant magnitudes at high indices, including odd offsets a float
    # rounds away (2^24 + 1 is the first unrepresentable int32 in fp32)
    for j, off in enumerate((1, 3, 1001, 4095)):
        x = x.at[0, 2 ** 24 + off].set(100.0 + j)
    v_ref, i_ref = ref.topk_compress_ref(x, 64)
    v, i = ops.topk_compress(x, 64, impl="pallas_interpret",
                             compaction="scan")
    assert int(np.asarray(i).max()) > 2 ** 24
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
    np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref), rtol=1e-6)


try:
    from hypothesis import given, settings
    import hypothesis.strategies as hst

    @settings(deadline=None, max_examples=10)
    @given(hst.integers(1, 4), hst.integers(1, 700), hst.integers(1, 100),
           hst.sampled_from([64, 128, 1024]), hst.booleans())
    def test_property_topk_scan_compaction_roundtrip(rows, n, k, block_n,
                                                     heavy):
        """Hypothesis sweep of the scan compaction against the oracle,
        including heavy-tailed rows (1e8 outlier next to ~1 values)."""
        k = min(k, n)
        x = jax.random.normal(jax.random.PRNGKey(n * 31 + k), (rows, n))
        if heavy:
            x = x.at[:, n // 2].set(1e8)
        v_ref, i_ref = ref.topk_compress_ref(x, k)
        v, i = ops.topk_compress(x, k, impl="pallas_interpret",
                                 block_n=block_n, compaction="scan")
        np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
        np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref),
                                   rtol=1e-6)
except ImportError:                                   # pragma: no cover
    pass


# ------------- codec kernels: batched QR + fused qint8 pack ---------- #

def _proj(q):
    """Projector QQ^T — the convention-free quantity PowerSGD consumes
    (the kernel's CGS2 column signs may differ from LAPACK's)."""
    return jnp.einsum("...ij,...kj->...ik", q, q)


@pytest.mark.parametrize("shape", [
    (1, 8, 2),
    (5, 33, 2),               # non-pow2 rows
    pytest.param((8, 96, 4), marks=_slow),
    (3, 57, 3),               # GQA-style odd panel dims
    (2, 7, 5),                # near-square, a barely >= r
    pytest.param((4, 2, 4, 78, 2), marks=_slow),   # extra batch dims
])
def test_batched_qr_interpret_matches_oracle(shape):
    """CGS2 kernel vs jnp.linalg.qr: projector parity plus
    orthonormality of the kernel's own Q."""
    p = jax.random.normal(jax.random.PRNGKey(sum(shape)), shape)
    q = ops.batched_qr(p, impl="pallas_interpret")
    q_ref = ref.batched_qr_ref(p)
    assert q.shape == p.shape and q.dtype == p.dtype
    np.testing.assert_allclose(np.asarray(_proj(q)),
                               np.asarray(_proj(q_ref)),
                               atol=5e-6, rtol=1e-5)
    r = shape[-1]
    gram = np.asarray(jnp.einsum("...ji,...jk->...ik", q, q))
    np.testing.assert_allclose(gram, np.broadcast_to(np.eye(r), gram.shape),
                               atol=5e-6)


def test_batched_qr_xla_impl_is_oracle():
    p = jax.random.normal(jax.random.PRNGKey(1), (3, 20, 2))
    np.testing.assert_array_equal(
        np.asarray(ops.batched_qr(p, impl="xla")),
        np.asarray(ref.batched_qr_ref(p)))


def test_batched_qr_rank_deficient_column_zero_not_nan():
    """A zero input column must come back as a ZERO Q column (the EF
    residual re-accumulates its mass), never NaNs from rsqrt(0); the
    surviving columns stay orthonormal."""
    p = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 3))
    p = p.at[..., 2].set(0.0)
    q = np.asarray(ops.batched_qr(p, impl="pallas_interpret"))
    assert np.isfinite(q).all()
    np.testing.assert_array_equal(q[..., 2], np.zeros_like(q[..., 2]))
    gram = np.einsum("bji,bjk->bik", q[..., :2], q[..., :2])
    np.testing.assert_allclose(gram, np.broadcast_to(np.eye(2), (2, 2, 2)),
                               atol=5e-6)


def test_batched_qr_rejects_wide_panels():
    with pytest.raises(ValueError, match="tall panel"):
        ops.batched_qr(jnp.zeros((2, 3, 5)), impl="pallas_interpret")


@pytest.mark.parametrize("rows,n,block", [
    (1, 37, 8),               # partial final block
    (5, 1000, 128),
    (2, 57, 16),              # GQA-style odd length
    (4, 128, 128),            # exact block multiple
    pytest.param(3, 4096, 256, marks=_slow),
])
def test_qint8_pack_bit_identical_under_jit(rows, n, block):
    """Fused pack/unpack (interpret) == oracle == the legacy two-pass
    quantizer, BIT-exact — all three under jit (XLA's eager constant
    folding of the /127 scale division differs by 1 ulp from the jitted
    program; reducers always run jitted)."""
    from repro.comm.quant import dequantize_block, quantize_block
    x = jax.random.normal(jax.random.PRNGKey(rows * n), (rows, n))
    pack_k = jax.jit(lambda x: ops.qint8_pack(x, block,
                                              impl="pallas_interpret"))
    pack_r = jax.jit(lambda x: ref.qint8_pack_ref(x, block))
    w_k, w_r = pack_k(x), pack_r(x)
    nb = -(-n // block)
    assert w_k.dtype == jnp.int8 and w_k.shape == (rows, nb, block + 4)
    np.testing.assert_array_equal(np.asarray(w_k), np.asarray(w_r))
    un_k = jax.jit(lambda w: ops.qint8_unpack(w, n,
                                              impl="pallas_interpret"))
    un_r = jax.jit(lambda w: ref.qint8_unpack_ref(w, n))
    got = np.asarray(un_k(w_k))
    np.testing.assert_array_equal(got, np.asarray(un_r(w_r)))
    legacy = jax.jit(
        lambda x: dequantize_block(*quantize_block(x, block), n))
    np.testing.assert_array_equal(got, np.asarray(legacy(x)))
    # round-trip error bound the reducer's docstring promises
    scale = np.abs(np.asarray(x)).max() / 127.0
    assert np.abs(got - np.asarray(x)).max() <= scale * 0.5 + 1e-7


def test_qint8_pack_xla_impl_is_oracle():
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 300))
    w = ops.qint8_pack(x, 64, impl="xla")
    np.testing.assert_array_equal(np.asarray(w),
                                  np.asarray(ref.qint8_pack_ref(x, 64)))
    np.testing.assert_array_equal(
        np.asarray(ops.qint8_unpack(w, 300, impl="xla")),
        np.asarray(ref.qint8_unpack_ref(w, 300)))


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
def test_qint8_pack_keeps_learner_axes(impl):
    """Stacked learner axes ``[P, G, S, n]`` pack row by row: the wire
    and the round trip equal those of the ``[P*G*S, n]`` rows, with the
    learner axes kept."""
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 2, 2, 300))
    pack = jax.jit(lambda x: ops.qint8_pack(x, 64, impl=impl))
    unpack = jax.jit(lambda w: ops.qint8_unpack(w, 300, impl=impl))
    w = pack(x)
    assert w.shape == (1, 2, 2, 5, 68)
    np.testing.assert_array_equal(
        np.asarray(w).reshape(4, 5, 68), np.asarray(pack(x.reshape(4, 300))))
    back = unpack(w)
    assert back.shape == x.shape
    np.testing.assert_array_equal(
        np.asarray(back).reshape(4, 300),
        np.asarray(unpack(w.reshape(4, 5, 68))))


try:
    from hypothesis import given, settings as _csettings
    import hypothesis.strategies as _cst

    @_csettings(deadline=None, max_examples=10)
    @given(_cst.integers(1, 6), _cst.integers(2, 600),
           _cst.integers(1, 4))
    def test_property_batched_qr_projector(batch, a, r):
        """Hypothesis sweep: projector parity on random tall panels,
        arbitrary (non-pow2, near-square) dims."""
        r = min(r, a)
        p = jax.random.normal(jax.random.PRNGKey(batch * 977 + a),
                              (batch, a, r))
        q = ops.batched_qr(p, impl="pallas_interpret")
        np.testing.assert_allclose(
            np.asarray(_proj(q)), np.asarray(_proj(ref.batched_qr_ref(p))),
            atol=1e-4, rtol=1e-4)

    @_csettings(deadline=None, max_examples=10)
    @given(_cst.integers(1, 4), _cst.integers(1, 512),
           _cst.sampled_from([8, 32, 128]))
    def test_property_qint8_pack_roundtrip(rows, n, block):
        """Hypothesis sweep: fused wire buffer bit-equal to the oracle
        and round-trip error inside the absmax/254 per-element bound."""
        x = jax.random.normal(jax.random.PRNGKey(rows * 401 + n),
                              (rows, n))
        pack = jax.jit(lambda x: ops.qint8_pack(x, block,
                                                impl="pallas_interpret"))
        un = jax.jit(lambda w: ops.qint8_unpack(w, n,
                                                impl="pallas_interpret"))
        ref_pack = jax.jit(lambda x: ref.qint8_pack_ref(x, block))
        np.testing.assert_array_equal(np.asarray(pack(x)),
                                      np.asarray(ref_pack(x)))
        got = np.asarray(un(pack(x)))
        scale = np.abs(np.asarray(x)).max() / 127.0
        assert np.abs(got - np.asarray(x)).max() <= scale * 0.5 + 1e-7
except ImportError:                                   # pragma: no cover
    pass


# ------------------------- flash decode ------------------------------ #

def _paged_case(key, b, hq, hkv, d, page, maxp, dtype=jnp.float32,
                shuffle=True, max_len=None):
    """Random paged-attention inputs with a scattered block table."""
    ks = jax.random.split(key, 3)
    n_pages = 1 + b * maxp
    q = jax.random.normal(ks[0], (b, hq, d), dtype)
    k_pages = jax.random.normal(ks[1], (hkv, n_pages, page, d), dtype)
    v_pages = jax.random.normal(ks[2], (hkv, n_pages, page, d), dtype)
    ids = np.arange(1, n_pages)
    if shuffle:   # physical pages deliberately out of sequence order
        ids = np.random.default_rng(b * 7 + maxp).permutation(ids)
    tables = jnp.asarray(ids.reshape(b, maxp).astype(np.int32))
    hi = max_len or maxp * page
    lengths = jnp.asarray(
        np.random.default_rng(d).integers(1, hi + 1, size=b), jnp.int32)
    return q, k_pages, v_pages, tables, lengths


@pytest.mark.parametrize("b,hq,hkv,d,page,maxp", [
    (1, 1, 1, 64, 8, 2),
    (2, 4, 2, 64, 8, 3),
    pytest.param(3, 8, 8, 32, 16, 2, marks=_slow),     # MHA (g=1)
    pytest.param(1, 6, 2, 128, 8, 4, marks=_slow),
    (2, 8, 2, 32, 16, 2),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_matches_ref(b, hq, hkv, d, page, maxp, dtype):
    """Paged decode kernel (interpret) == XLA gather oracle, through a
    shuffled block table and ragged per-sequence lengths."""
    q, kp, vp, tbl, lens = _paged_case(jax.random.PRNGKey(0), b, hq, hkv,
                                       d, page, maxp, dtype)
    o_ref = ref.flash_decode_ref(q, kp, vp, tbl, lens)
    o = ops.flash_decode(q, kp, vp, tbl, lens, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("window", [1, 5, 16, 100])
def test_flash_decode_window(window):
    """Sliding-window masking incl. pages that short-circuit entirely
    out of the window."""
    q, kp, vp, tbl, lens = _paged_case(jax.random.PRNGKey(1), 2, 4, 2, 64,
                                       8, 4)
    o_ref = ref.flash_decode_ref(q, kp, vp, tbl, lens, window=window)
    o = ops.flash_decode(q, kp, vp, tbl, lens, window=window,
                         impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5,
                               rtol=2e-5)


def test_flash_decode_inactive_slots_zero():
    """lengths == 0 (inactive serving slots) must output exact zeros in
    both the oracle and the kernel — not NaNs from an empty softmax."""
    q, kp, vp, tbl, lens = _paged_case(jax.random.PRNGKey(2), 3, 4, 2, 32,
                                       8, 2)
    lens = lens.at[1].set(0)
    for impl in ("xla", "pallas_interpret"):
        o = np.asarray(ops.flash_decode(q, kp, vp, tbl, lens, impl=impl))
        assert np.isfinite(o).all()
        np.testing.assert_array_equal(o[1], np.zeros_like(o[1]))


def test_flash_decode_null_page_tail_ignored():
    """Unallocated block-table tail entries point at the null page 0;
    whatever garbage lives there must not leak into masked positions."""
    q, kp, vp, tbl, lens = _paged_case(jax.random.PRNGKey(3), 2, 4, 2, 32,
                                       8, 3, max_len=8)
    # sequences fit in page 0 of their table; null out the tail entries
    tbl0 = tbl.at[:, 1:].set(0)
    kp = kp.at[:, 0].set(1e6)            # poison the null page
    vp = vp.at[:, 0].set(-1e6)
    o_ref = ref.flash_decode_ref(q, kp, vp, tbl0, lens)
    o = ops.flash_decode(q, kp, vp, tbl0, lens, impl="pallas_interpret")
    assert np.isfinite(np.asarray(o)).all()
    assert np.abs(np.asarray(o)).max() < 1e3
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5,
                               rtol=2e-5)


def test_flash_decode_gather_pages_roundtrip():
    """gather_pages (the oracle's dense materialization) inverts the
    paged layout: writing token t of sequence b to page tbl[b, t//page]
    offset t%page reads back at dense position t."""
    b, hkv, d, page, maxp = 2, 2, 16, 4, 3
    n_pages = 1 + b * maxp
    pages = jnp.zeros((hkv, n_pages, page, d))
    tbl = jnp.asarray(np.arange(1, n_pages).reshape(b, maxp).astype(np.int32))
    val = jax.random.normal(jax.random.PRNGKey(4), (b, maxp * page, hkv, d))
    for t in range(maxp * page):
        pages = pages.at[:, tbl[:, t // page], t % page].set(
            val[:, t].transpose(1, 0, 2))
    dense = ref.gather_pages(pages, tbl)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(val))


try:
    from hypothesis import given, settings as hsettings
    import hypothesis.strategies as _hst

    @hsettings(deadline=None, max_examples=8)
    @given(_hst.integers(1, 3), _hst.sampled_from([1, 2, 4]),
           _hst.sampled_from([32, 64, 128]), _hst.sampled_from([8, 16]),
           _hst.integers(1, 3), _hst.integers(0, 12))
    def test_property_flash_decode(b, g, d, page, maxp, window):
        """Hypothesis sweep over head_dim / page size / pages-per-seq /
        GQA group / window against the oracle."""
        hkv = 2
        q, kp, vp, tbl, lens = _paged_case(
            jax.random.PRNGKey(b * 131 + d + page), b, hkv * g, hkv, d,
            page, maxp)
        o_ref = ref.flash_decode_ref(q, kp, vp, tbl, lens, window=window)
        o = ops.flash_decode(q, kp, vp, tbl, lens, window=window,
                             impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   atol=2e-5, rtol=2e-5)
except ImportError:                                   # pragma: no cover
    pass


@pytest.mark.parametrize("b,s,h,d", [
    (1, 64, 1, 64),
    pytest.param(2, 128, 3, 64, marks=_slow),
    pytest.param(1, 192, 2, 128, marks=_slow),
    (2, 64, 4, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rwkv6_wkv(b, s, h, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    r = (0.5 * jax.random.normal(ks[0], (b, s, h, d))).astype(dtype)
    k = (0.5 * jax.random.normal(ks[1], (b, s, h, d))).astype(dtype)
    v = (0.5 * jax.random.normal(ks[2], (b, s, h, d))).astype(dtype)
    w = (jax.nn.sigmoid(jax.random.normal(ks[3], (b, s, h, d))) * 0.4
         + 0.55).astype(jnp.float32)
    u = 0.1 * jax.random.normal(ks[4], (h, d))
    s0 = jnp.zeros((b, h, d, d))
    y_ref, sT_ref = ref.rwkv6_wkv_ref(r, k, v, w, u, s0)
    y, sT = ops.rwkv6_wkv(r, k, v, w, u, s0, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=_tol(dtype) * 4, rtol=_tol(dtype) * 4)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sT_ref),
                               atol=1e-4, rtol=1e-4)


def test_rwkv6_wkv_chunking_and_state_resume():
    """Chunked kernel == oracle, and resuming from the midpoint state equals
    one continuous run (decode-path correctness)."""
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    b, s, h, d = 1, 128, 2, 64
    r = 0.5 * jax.random.normal(ks[0], (b, s, h, d))
    k = 0.5 * jax.random.normal(ks[1], (b, s, h, d))
    v = 0.5 * jax.random.normal(ks[2], (b, s, h, d))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, s, h, d))) * 0.4 + 0.55
    u = 0.1 * jax.random.normal(ks[4], (h, d))
    s0 = jnp.zeros((b, h, d, d))
    y_all, sT_all = ref.rwkv6_wkv_ref(r, k, v, w, u, s0)
    # two halves via the kernel, threading the state
    y1, s_mid = ops.rwkv6_wkv(r[:, :64], k[:, :64], v[:, :64], w[:, :64],
                              u, s0, impl="pallas_interpret", block_t=32)
    y2, sT = ops.rwkv6_wkv(r[:, 64:], k[:, 64:], v[:, 64:], w[:, 64:],
                           u, s_mid, impl="pallas_interpret", block_t=32)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_all), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sT_all),
                               atol=1e-4, rtol=1e-4)


def test_wkv_kernel_matches_model_decode_semantics():
    """Kernel recurrence equals the per-token decode formula in rwkv6.py."""
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    b, h, d = 2, 2, 64
    s = 8
    r = 0.5 * jax.random.normal(ks[0], (b, s, h, d))
    k = 0.5 * jax.random.normal(ks[1], (b, s, h, d))
    v = 0.5 * jax.random.normal(ks[2], (b, s, h, d))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, s, h, d))) * 0.4 + 0.55
    u = 0.1 * jax.random.normal(ks[4], (h, d))
    S = jnp.zeros((b, h, d, d))
    ys = []
    for t in range(s):
        kv = k[:, t][..., :, None] * v[:, t][..., None, :]
        y = jnp.einsum("bhj,bhji->bhi", r[:, t], S + u[None, :, :, None] * kv)
        S = w[:, t][..., :, None] * S + kv
        ys.append(y)
    y_manual = jnp.stack(ys, 1)
    y_k, S_k = ops.rwkv6_wkv(r, k, v, w, u, jnp.zeros((b, h, d, d)),
                             impl="pallas_interpret", block_t=8)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_manual),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(S_k), np.asarray(S), atol=1e-4,
                               rtol=1e-4)


_PER_ROW_CHILD = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.kernels import ops, ref
mesh = Mesh(np.array(jax.devices()).reshape(1, 2, 2, 2, 1),
            ("pod", "group", "local", "fsdp", "model"))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 1024))
L = ("pod", "group", "local")
cases = [  # (fn, operand, trailing dims, operand placement)
    (lambda a: ref.qint8_pack_ref(a, 128), x[:4], 1, P(L)),     # learners
    (lambda a: ref.topk_compress_ref(a, 16), x, 1, P(L + ("fsdp",))),
    (ref.batched_qr_ref, x[:4].reshape(1, 2, 2, 256, 4), 2, P(*L)),
]
for fn, a, t, spec in cases:
    a = jax.device_put(a, NamedSharding(mesh, spec))
    def body(v, fn=fn, t=t):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return ops._per_row(fn, v, t)
    got, want = jax.jit(body)(a), jax.jit(fn)(a)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and bool(jnp.array_equal(g, w)), fn
    assert "manual_computation" in jax.jit(body).lower(a).as_text()
    bare = jax.jit(lambda v, fn=fn, t=t: ops._per_row(fn, v, t))
    assert "manual_computation" not in bare.lower(a).as_text()
print("ok")
"""


def test_per_row_splits_compiled_kernels_over_the_learner_mesh():
    """Mosaic kernels cannot be partitioned by the compiler, so under a
    learner mesh ``ops`` runs them in a shard_map over the rows each
    device holds — learner rows, or learner x fsdp rows of a codec view —
    and calls them as is without one.  Row-wise oracles stand in for the
    kernels: the split must not change a value."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", _PER_ROW_CHILD], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0 and r.stdout.strip() == "ok", r.stderr[-3000:]
