"""Which attention calls take the fused kernel (kernels/flash_attention.py)
and which stay on XLA, as ``models/attention.full_attention`` records
them while it traces.

Calls are only traced (``jax.eval_shape``), never lowered, so the TPU
backend the dispatch asks for can be stood in for on the CPU.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh

from repro.kernels import flash_attention as fa
from repro.models import attention as A

S, HQ, HKV, D = 1024, 4, 2, 64


def _spec(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


QKV = (_spec(1, S, HQ, D), _spec(1, S, HKV, D), _spec(1, S, HKV, D))


def _record(fn, *specs):
    with A.DispatchRecord() as rec:
        jax.eval_shape(fn, *specs)
    return rec


@pytest.fixture
def tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _decode(q, k, v):
    """One dense-cache decode step of a GQA layer."""
    p = A.gqa_init(jax.random.PRNGKey(0), 64, HQ, HKV, D)
    cache = A.init_kv_cache(1, S, HKV, D, jnp.float32)
    x = jnp.zeros((1, 1, 64))
    return A.gqa_decode(p, x, cache, None, None, n_heads=HQ,
                        n_kv_heads=HKV, head_dim=D)


def _under_mesh(q, k, v):
    mesh = AbstractMesh((1, 2, 2), ("pod", "group", "local"))
    with jax.sharding.use_abstract_mesh(mesh):
        return A.full_attention(q, k, v)


BYPASS = {
    "not causal": (lambda q, k, v: A.full_attention(q, k, v, causal=False),
                   QKV),
    "extra mask": (lambda q, k, v: A.full_attention(
        q, k, v, extra_mask=jnp.ones((1, 1, S, S), bool)), QKV),
    "query offset": (lambda q, k, v, base: A.full_attention(
        q, k, v, q_offset=base), QKV + (_spec(dtype=jnp.int32),)),
    "queries != keys": (A.full_attention,
                        (_spec(1, 128, HQ, D),) + QKV[1:]),
    "sequence 1000 does not tile": (
        A.full_attention, (_spec(1, 1000, HQ, D), _spec(1, 1000, HKV, D),
                           _spec(1, 1000, HKV, D))),
    "head_dim 32": (A.full_attention, (_spec(1, S, HQ, 32),
                                       _spec(1, S, HKV, 32),
                                       _spec(1, S, HKV, 32))),
    # 128 query heads of 128 on one kv head overflow VMEM at any block
    "VMEM: 128 query heads a kv head": (
        A.full_attention, (_spec(1, S, 128, 128), _spec(1, S, 1, 128),
                           _spec(1, S, 1, 128))),
    "under a mesh": (_under_mesh, QKV),
}


@pytest.mark.parametrize("why", list(BYPASS) + ["decode"])
def test_call_stays_on_xla(tpu, why):
    fn, specs = (_decode, QKV) if why == "decode" else BYPASS[why]
    rec = _record(fn, *specs)
    assert [(c.path, c.why) for c in rec.calls] == \
        [("xla", "not causal" if why == "decode" else why)]
    assert "fused in 0 of 1 traced calls" in rec.describe()


def test_call_stays_on_xla_off_the_tpu():
    assert jax.default_backend() != "tpu"
    (call,) = _record(lambda q, k, v: A.full_attention(q, k, v, window=300),
                      *QKV).calls
    assert (call.path, call.why) == ("xla", "not a TPU")


@pytest.mark.parametrize("window", [0, 300])
def test_qualifying_call_takes_the_kernel_and_reports_its_tiles(tpu, window):
    rec = _record(lambda q, k, v: A.full_attention(q, k, v, window=window),
                  *QKV)
    (call,) = rec.calls
    assert call.path == "fused"
    b = fa.blocks(S, window, HQ // HKV, D)
    n = S // b
    # the causal (windowed) grid's tiles with a visible key
    want = sum(1 for i in range(n) for j in range(n)
               if j <= i and (not window or (i - j - 1) * b + 1 < window))
    assert (call.key_tiles, call.all_tiles) == (want, n * n)
    assert call.key_tiles < call.all_tiles
    assert rec.describe() == (
        f"attention: fused in 1 of 1 traced calls, {want} of {n * n} key "
        f"tiles ({100 * want / (n * n):.1f}%)")


def test_a_scanned_stack_is_one_traced_call(tpu):
    """A layer stack under ``lax.scan`` traces its body once: one call
    stands for every layer of the stack."""
    def stack(q, k, v):
        def body(x, _):
            return A.full_attention(x, k, v), None
        return jax.lax.scan(body, q, jnp.zeros((5, 1)))[0]

    rec = _record(stack, *QKV)
    assert [c.path for c in rec.calls] == ["fused"]
    assert rec.describe().startswith(
        "attention: fused in 1 of 1 traced calls, ")
