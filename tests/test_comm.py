"""Reducer subsystem (comm/): codec round-trip bounds, error-feedback
residual behavior, the avg_dtype -> cast regression, and compressed
Hier-AVG convergence vs the dense mean."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import (CastReducer, EFState, MeanReducer, QInt8Reducer,
                        RandKReducer, Reducer, TopKReducer, get_reducer,
                        reduce_with)
from repro.comm.quant import dequantize_block, quantize_block
from repro.configs.base import HierAvgParams
from repro.core import (HierTopology, Simulator, global_average, init_state,
                        local_average, make_hier_round)
from repro.optim import sgd


def _tree(key, topo, shapes=((6, 5), (7,), (3, 4, 2))):
    ks = jax.random.split(key, len(shapes))
    return {f"w{i}": jax.random.normal(k, topo.shape + s)
            for i, (k, s) in enumerate(zip(ks, shapes))}


# ------------------------------ registry ------------------------------ #

def test_get_reducer_specs():
    assert isinstance(get_reducer("mean"), MeanReducer)
    assert get_reducer("cast").payload_dtype == jnp.bfloat16
    assert get_reducer("cast:float16").payload_dtype == jnp.float16
    assert get_reducer("topk:0.05").ratio == 0.05
    assert get_reducer("randk").ratio == 0.1
    assert get_reducer("qint8:128").block == 128
    r = get_reducer("topk:0.2")
    assert get_reducer(r) is r          # instances pass through
    with pytest.raises(ValueError):
        get_reducer("gzip")
    with pytest.raises(ValueError):
        HierAvgParams(k1=2, k2=4, reducer="gzip")


# ------------------------------ mean / cast --------------------------- #

def test_mean_reducer_is_identity_average():
    topo = HierTopology(1, 2, 2)
    tree = _tree(jax.random.PRNGKey(0), topo)
    red = MeanReducer()
    out, st = reduce_with(red, global_average, tree, red.init_state(tree))
    expect = global_average(tree)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(expect)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert st == ()


def test_cast_reducer_matches_legacy_avg_dtype():
    """Regression: the removed ``avg_dtype=jnp.bfloat16`` path is exactly
    the "cast:bfloat16" reducer (narrow, mean in the narrow dtype, widen)."""
    topo = HierTopology(2, 2, 2)
    tree = _tree(jax.random.PRNGKey(1), topo)

    def legacy_avg_dtype(avg_fn, tree, avg_dtype):  # the old _avg body
        dtypes = jax.tree.map(lambda x: x.dtype, tree)
        narrowed = jax.tree.map(lambda x: x.astype(avg_dtype), tree)
        out = avg_fn(narrowed, None)
        return jax.tree.map(lambda x, d: x.astype(d), out, dtypes)

    red = CastReducer(jnp.bfloat16)
    for avg_fn in (local_average, global_average):
        want = legacy_avg_dtype(avg_fn, tree, jnp.bfloat16)
        got, _ = reduce_with(red, avg_fn, tree, ())
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cast_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, 512))
    red = CastReducer(jnp.bfloat16)
    payload, _ = red.compress({"w": x}, ())
    back = red.decompress(payload, {"w": x}, ())["w"].astype(jnp.float32)
    # bf16 keeps 8 mantissa bits -> relative error < 2^-8
    rel = np.abs(np.asarray(back - x)) / np.maximum(np.abs(np.asarray(x)),
                                                    1e-6)
    assert rel.max() < 2.0 ** -8


# ------------------------------ qint8 --------------------------------- #

def test_qint8_roundtrip_error_bound():
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(3), (4, 1000))
    q, scale = quantize_block(x, block=128)
    back = dequantize_block(q, scale, 1000)
    # error <= scale/2 per element, scale = blockwise absmax / 127
    err = np.abs(np.asarray(back) - np.asarray(x))
    bound = np.repeat(np.asarray(scale)[:, :, 0], 128, axis=1)[:, :1000] / 2
    assert (err <= bound + 1e-7).all()


def test_qint8_payload_accounting():
    tree = {"w": jnp.zeros((1000,)), "b": jnp.zeros((10,))}
    twopass = QInt8Reducer(block=128, fused=False)
    # 1000 -> 1000 B + 8 scales * 4 B ; 10 -> 10 B + 1 scale * 4 B
    assert twopass.payload_bytes(tree) == 1000 + 32 + 10 + 4
    dense = MeanReducer().payload_bytes(tree)
    assert dense == 4040 and dense / twopass.payload_bytes(tree) > 3.8
    # the fused pack ships whole (block + 4 B scale) wire blocks, zero
    # tail included: 8 blocks for w, 1 for b — honestly billed
    fused = QInt8Reducer(block=128)
    assert fused.payload_bytes(tree) == (8 + 1) * (128 + 4)
    assert dense / fused.payload_bytes(tree) > 3.3
    # and collapses the per-reduction message count 2 -> 1 per leaf
    assert fused.n_messages(tree) == 2 and twopass.n_messages(tree) == 4
    # spec round-trip for both wire layouts
    assert get_reducer("qint8:128").describe() == "qint8:128"
    assert get_reducer("qint8:128:twopass").describe() \
        == "qint8:128:twopass"
    assert get_reducer("qint8:twopass").block == 256
    assert not get_reducer("qint8:twopass").fused


def test_qint8_fused_reduction_matches_twopass_bitwise():
    """The fused single-buffer wire format is a PACKING change only:
    under jit (reducers always run jitted) the dequantized values are
    bit-identical to the legacy two-pass quantize path.  The reduction
    then agrees bitwise over a pair of learners; over four, XLA may
    associate the two programs' sums differently (tests/test_bucket.py)."""
    key = jax.random.PRNGKey(9)
    for topo in (HierTopology(1, 2, 2), HierTopology(1, 1, 2)):
        tree = {"w": jax.random.normal(key, topo.shape + (13, 7)),
                "b": jax.random.normal(jax.random.fold_in(key, 1),
                                       topo.shape + (37,))}
        fused, twopass = (get_reducer(s) for s in ("qint8:32",
                                                   "qint8:32:twopass"))
        x_f, x_t = (jax.jit(lambda t, r=r: r.decompress(
            r.compress(t, ())[0], t, ()))(tree) for r in (fused, twopass))
        for k in tree:
            np.testing.assert_array_equal(np.asarray(x_f[k]),
                                          np.asarray(x_t[k]))
    out_f, _ = jax.jit(lambda t: reduce_with(
        fused, global_average, t, ()))(tree)
    out_t, _ = jax.jit(lambda t: reduce_with(
        twopass, global_average, t, ()))(tree)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(out_f[k]),
                                      np.asarray(out_t[k]))


# ------------------------------ sparse + EF --------------------------- #

def test_topk_selects_largest_and_updates_residual():
    topo = HierTopology(1, 1, 2)
    x = jax.random.normal(jax.random.PRNGKey(4), topo.shape + (100,))
    red = TopKReducer(ratio=0.1)
    st = red.init_state({"w": jnp.zeros_like(x)})  # ref=0 -> delta == x
    payload, st = red.compress({"w": x}, st)
    vals, idx = payload[0]
    assert vals.shape == (2, 10) and idx.shape == (2, 10)
    # transmitted coordinates are the 10 largest |x| per learner
    flat = np.abs(np.asarray(x).reshape(2, 100))
    for r in range(2):
        want = set(np.argsort(-flat[r])[:10].tolist())
        assert set(np.asarray(idx)[r].tolist()) == want
    # residual holds exactly the untransmitted mass
    err = np.asarray(jax.tree.leaves(st.err)[0]).reshape(2, 100)
    dense = np.zeros((2, 100), np.float32)
    for r in range(2):
        dense[r, np.asarray(idx)[r]] = np.asarray(vals)[r]
    np.testing.assert_allclose(err, np.asarray(x).reshape(2, 100) - dense,
                               atol=1e-6)


def test_randk_shared_support():
    topo = HierTopology(1, 1, 4)
    x = jax.random.normal(jax.random.PRNGKey(5), topo.shape + (50,))
    red = RandKReducer(ratio=0.2)
    st = red.init_state({"w": jnp.zeros_like(x)})
    (vals, idx), = red.compress({"w": x}, st)[0]
    assert idx.shape == (4, 10)
    # every learner transmits the same support
    assert (np.asarray(idx) == np.asarray(idx)[0:1]).all()


def test_topk_error_feedback_residual_stays_bounded(cls_task):
    """EF residual norms stay small relative to the params over many
    rounds (the residual is re-injected, not accumulated unboundedly)."""
    topo = HierTopology(1, 2, 2)
    h = HierAvgParams(k1=2, k2=4)
    opt = sgd(0.05)
    red = TopKReducer(ratio=0.1)
    round_fn = jax.jit(make_hier_round(cls_task["loss_fn"], opt, h,
                                       reducer=red))
    state = init_state(topo, cls_task["init_fn"], opt,
                       jax.random.PRNGKey(0), reducer=red)
    key = jax.random.PRNGKey(1)
    norms = []
    for _ in range(8):
        key, kb = jax.random.split(key)
        batch = cls_task["sample"](kb, h.k2 * topo.n_learners * 8)
        shaped = jax.tree.map(
            lambda x: x.reshape((h.beta, h.k1) + topo.shape + (8,)
                                + x.shape[1:]), batch)
        state, _ = round_fn(state, shaped)
        # comm_state is keyed by plan level (local/global EF are separate)
        err_sq = sum(float(jnp.sum(jnp.square(l)))
                     for lvl in state.comm_state.values()
                     for l in jax.tree.leaves(lvl.err))
        norms.append(err_sq ** 0.5)
    p_norm = sum(float(jnp.sum(jnp.square(l)))
                 for l in jax.tree.leaves(state.params)) ** 0.5
    assert all(n < 0.5 * p_norm for n in norms), (norms, p_norm)
    # no monotone blow-up: the late residuals are no larger than 2x any
    # earlier plateau
    assert norms[-1] < 2.0 * max(norms[:4]) + 1e-3, norms


def test_hier_round_with_topk_keeps_global_consensus(cls_task):
    """After the (compressed) global reduction all P learners agree."""
    topo = HierTopology(1, 2, 2)
    h = HierAvgParams(k1=2, k2=4)
    opt = sgd(0.05)
    red = TopKReducer(ratio=0.25)
    round_fn = jax.jit(make_hier_round(cls_task["loss_fn"], opt, h,
                                       reducer=red))
    state = init_state(topo, cls_task["init_fn"], opt,
                       jax.random.PRNGKey(0), reducer=red)
    batch = cls_task["sample"](jax.random.PRNGKey(1),
                               h.k2 * topo.n_learners * 8)
    shaped = jax.tree.map(
        lambda x: x.reshape((h.beta, h.k1) + topo.shape + (8,)
                            + x.shape[1:]), batch)
    state, _ = round_fn(state, shaped)
    for leaf in jax.tree.leaves(state.params):
        flat = leaf.reshape((topo.n_learners,) + leaf.shape[3:])
        assert bool(jnp.allclose(flat, flat[0:1], atol=1e-6))


def test_step_api_with_reducer_keeps_consensus(cls_task):
    """The masked step API threads/blends per-level comm_state correctly:
    compress runs every step but each level's EF state and the params only
    change on that level's reduction steps, and the K2 boundary still ends
    in global consensus."""
    from repro.core import make_hier_step
    topo = HierTopology(1, 2, 2)
    h = HierAvgParams(k1=2, k2=4)
    opt = sgd(0.05)
    red = TopKReducer(ratio=0.25)
    step_fn = jax.jit(make_hier_step(cls_task["loss_fn"], opt, h,
                                     reducer=red))
    state = init_state(topo, cls_task["init_fn"], opt,
                       jax.random.PRNGKey(0), reducer=red)
    refs = {name: jax.tree.leaves(lvl.ref)[0]
            for name, lvl in state.comm_state.items()}
    key = jax.random.PRNGKey(1)
    for t in range(1, h.k2 + 1):
        key, kb = jax.random.split(key)
        batch = cls_task["sample"](kb, topo.n_learners * 8)
        shaped = jax.tree.map(
            lambda x: x.reshape(topo.shape + (8,) + x.shape[1:]), batch)
        state, _ = step_fn(state, shaped)
        now = {name: jax.tree.leaves(lvl.ref)[0]
               for name, lvl in state.comm_state.items()}
        fired = {"local": t % h.k1 == 0 and t % h.k2 != 0,
                 "global": t % h.k2 == 0}
        for name in refs:
            if fired[name]:
                refs[name] = now[name]
            else:   # this level did not reduce -> its EF ref untouched
                assert bool(jnp.allclose(now[name], refs[name], atol=0)), \
                    (name, t)
    for leaf in jax.tree.leaves(state.params):
        flat = leaf.reshape((topo.n_learners,) + leaf.shape[3:])
        assert bool(jnp.allclose(flat, flat[0:1], atol=1e-6))


# ------------------------------ convergence --------------------------- #

@pytest.mark.slow
@pytest.mark.parametrize("spec,tol", [
    ("cast:bfloat16", 0.02), ("qint8:128", 0.02), ("topk:0.1", 0.02),
    # random-k is the weakest selector: with honest PER-LEVEL error
    # feedback (the global reference is the last global consensus, not a
    # free ride on the dense local refs as before the ReductionPlan
    # refactor) its global coverage is only `ratio` of coordinates per
    # round, so it needs a larger ratio / looser bar.  Bucketed (the
    # default) draws ONE shared support over the whole flat model — the
    # textbook random-k of Stich et al. — which loses the per-leaf
    # stratification freebie (a small bias leaf can go unsampled for
    # rounds, riding the EF residual), hence the wider bar vs ":perleaf".
    ("randk:0.25", 0.05),
    ("randk:0.25:perleaf", 0.03),
])
def test_reducer_hier_avg_near_dense(cls_task, spec, tol):
    """Compressed Hier-AVG reaches near-dense eval accuracy."""
    topo = HierTopology(1, 2, 4)
    h = HierAvgParams(k1=2, k2=8)
    kw = dict(topo=topo, hier=h, optimizer=sgd(0.1), seed=1,
              eval_batch=cls_task["eval_batch"], per_learner_batch=16)
    dense = Simulator(cls_task["loss_fn"], cls_task["init_fn"],
                      cls_task["sample"], reducer="mean", **kw).run(10)
    comp = Simulator(cls_task["loss_fn"], cls_task["init_fn"],
                     cls_task["sample"], reducer=spec, **kw).run(10)
    assert comp.final_eval_acc >= dense.final_eval_acc - tol, (
        spec, comp.final_eval_acc, dense.final_eval_acc)


def test_payload_reduction_factors(cls_task):
    """topk(10%) cuts the global-reduction payload >= 4x vs dense."""
    topo = HierTopology(1, 2, 2)
    h = HierAvgParams(k1=2, k2=4)
    kw = dict(topo=topo, hier=h, eval_batch=None, per_learner_batch=8)
    dense = Simulator(cls_task["loss_fn"], cls_task["init_fn"],
                      cls_task["sample"], reducer="mean", **kw)
    topk = Simulator(cls_task["loss_fn"], cls_task["init_fn"],
                     cls_task["sample"], reducer="topk:0.1", **kw)
    ratio = (dense.payload_bytes_per_reduction()
             / topk.payload_bytes_per_reduction())
    assert ratio >= 4.0, ratio
