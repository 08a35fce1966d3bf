"""Bucketed flat-buffer reductions (comm/bucket.py): layout construction,
pack/unpack round-trips (property-tested over dtype-mixed pytrees and
model-zoo param shapes), bit-exactness of bucketed mean/cast vs the
per-leaf path across a 3-level plan, the global-k topk oracle, and the
layout-checked EF state init."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import (Bucketed, BucketLayout, EFState, Pipelined,
                        get_reducer, reduce_with)
from repro.configs.base import HierAvgParams
from repro.core import (HierTopology, Simulator, global_average, init_state,
                        make_hier_round, resolve_plan)
from repro.core.hier_avg import make_hier_step, shard_round_batch
from repro.core.topology import stack_like
from repro.optim import sgd

TOPO = HierTopology(1, 2, 2)
# A mean over two learners has one association (a + b), so two programs
# that compute the same mean agree bit for bit.  Over four learners XLA
# chooses the summation order per fusion (sequential when a trailing dim
# is kept, pairwise when the whole leaf is reduced), and the same mean
# can differ in its last bit between a leaf and the bucket it is packed
# into.
PAIR = HierTopology(1, 1, 2)


def _assert_same_mean(got, want, leaf, topo, wire=jnp.float32):
    """Two programs' learner-axis means of ``leaf``: bit-identical over a
    pair; over more learners, apart by no more than two summation orders
    can be — (n - 1) roundings each, at the coarser of the leaf's and the
    wire's precision, of the mean's magnitude sum."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if topo.n_learners == 2:
        np.testing.assert_array_equal(got, want)
        return
    eps = max(float(jnp.finfo(leaf.dtype).eps), float(jnp.finfo(wire).eps))
    mag = np.mean(np.abs(np.asarray(leaf, np.float64)),
                  axis=tuple(range(len(topo.shape))))
    assert np.all(np.abs(got - want)
                  <= (topo.n_learners - 1) * eps * mag), (got, want)


def _mixed_tree(topo=TOPO):
    key = jax.random.PRNGKey(0)
    mk = lambda i, s, d=jnp.float32: jax.random.normal(  # noqa: E731
        jax.random.fold_in(key, i), topo.shape + s).astype(d)
    return {
        "w0": mk(0, (6, 5)),
        "b0": mk(1, (7,)),
        "h": mk(2, (3, 4, 2), jnp.bfloat16),
        "scalar": mk(3, ()),
        "w1": mk(4, (8, 3), jnp.bfloat16),
    }


# ------------------------------ layout -------------------------------- #

def test_layout_groups_by_dtype_and_caps_size():
    tree = _mixed_tree()
    lay = BucketLayout.build(tree)        # uncapped in practice (4 MiB)
    assert lay.n_leaves == 5
    by_dtype = {b.dtype: b for b in lay.buckets}
    assert set(by_dtype) == {"float32", "bfloat16"}
    assert by_dtype["float32"].size == 6 * 5 + 7 + 1
    assert by_dtype["bfloat16"].size == 3 * 4 * 2 + 8 * 3
    # a tight cap splits the float32 group; leaves are never split, and an
    # over-cap leaf (w0: 30 elements > 8-element cap) gets its own bucket
    # (dict leaves flatten in sorted key order: b0, scalar, w0)
    tight = BucketLayout.build(tree, bucket_bytes=8 * 4)
    f32 = [b for b in tight.buckets if b.dtype == "float32"]
    assert [b.size for b in f32] == [8, 30]
    # slots record exact offsets within their bucket
    assert [(s.offset, s.size) for s in f32[0].slots] == [(0, 7), (7, 1)]
    assert f32[1].slots[0].size == 30


def test_pack_unpack_roundtrip_mixed_dtypes():
    tree = _mixed_tree()
    for bucket_bytes in (0, 16, 4 << 20):
        lay = BucketLayout.build(tree, bucket_bytes=bucket_bytes)
        back = lay.unpack(lay.pack(tree))
        for k in tree:
            assert back[k].dtype == tree[k].dtype
            np.testing.assert_array_equal(np.asarray(back[k]),
                                          np.asarray(tree[k]))


def test_matrix_mode_pads_and_roundtrips():
    tree = _mixed_tree()
    lay = BucketLayout.build(tree, matrix=True)
    for b in lay.buckets:
        assert len(b.shape) == 2 and b.padded_size >= b.size
    back = lay.unpack(lay.pack(tree))
    for k in tree:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(tree[k]))


def test_model_zoo_param_shapes_roundtrip():
    """Real model-zoo param pytrees (reduced configs, eval_shape only — no
    arrays) survive pack/unpack with shapes and dtypes intact."""
    from repro.configs import get_config
    from repro.models import build
    for arch in ("hymba-1.5b", "deepseek-v2-lite-16b"):
        bundle = build(get_config(arch).reduced())
        params1 = jax.eval_shape(bundle.init,
                                 jax.ShapeDtypeStruct((2,), jnp.uint32))
        params = jax.eval_shape(lambda p: stack_like(TOPO, p), params1)
        lay = BucketLayout.build(params)
        assert lay.n_buckets < lay.n_leaves
        out = jax.eval_shape(lambda t: lay.unpack(lay.pack(t)), params)
        assert (jax.tree.map(lambda l: (l.shape, l.dtype), out)
                == jax.tree.map(lambda l: (l.shape, l.dtype), params))


# --------------------- hypothesis property tests ---------------------- #

try:
    import hypothesis
    import hypothesis.strategies as st
    from hypothesis import given, settings

    _HYP = True

    leaf_shapes = st.lists(
        st.tuples(st.sampled_from([(3,), (2, 4), (5,), (1, 2, 3), ()]),
                  st.sampled_from(["float32", "bfloat16", "float16"])),
        min_size=1, max_size=6)

    @settings(deadline=None, max_examples=25)
    @given(leaf_shapes, st.integers(0, 64),
           st.tuples(st.integers(1, 2), st.integers(1, 2),
                     st.integers(1, 3)))
    def test_property_pack_unpack_roundtrip(leaves, cap, topo_shape):
        tree = {}
        for i, (shape, dtype) in enumerate(leaves):
            n = int(np.prod(topo_shape + shape)) if shape \
                else int(np.prod(topo_shape))
            tree[f"l{i}"] = (jnp.arange(n, dtype=jnp.float32)
                             .reshape(topo_shape + shape)
                             .astype(dtype))
        lay = BucketLayout.build(tree, bucket_bytes=cap)
        back = lay.unpack(lay.pack(tree))
        for k in tree:
            assert back[k].dtype == tree[k].dtype
            np.testing.assert_array_equal(np.asarray(back[k]),
                                          np.asarray(tree[k]))
        # every element lands in exactly one slot of one bucket
        assert sum(b.size for b in lay.buckets) \
            == sum(int(np.prod(topo_shape + s)) // int(np.prod(topo_shape))
                   for s, _ in leaves)
except ImportError:                                   # pragma: no cover
    _HYP = False


# ----------------------- bucketed reducer parity ---------------------- #

def test_bucketed_mean_and_cast_bit_identical_single_reduction():
    """Packing permutes no values: bucketed and per-leaf means agree bit
    for bit over a pair, and to summation order over four learners."""
    for topo in (PAIR, TOPO):
        tree = _mixed_tree(topo)
        for spec, wire in (("mean", jnp.float32),
                           ("cast:bfloat16", jnp.bfloat16)):
            per_leaf, _ = reduce_with(get_reducer(spec), global_average,
                                      tree, ())
            bucketed, _ = reduce_with(Bucketed(get_reducer(spec)),
                                      global_average, tree, ())
            for k in tree:
                _assert_same_mean(bucketed[k], per_leaf[k], tree[k], topo,
                                  wire)


def test_bucketed_cast_bit_identical_across_3level_plan(cls_task):
    """Full-trajectory bit-exactness: a 3-level cast/mean plan trained
    with bucketing on vs off (per-leaf) gives byte-identical params."""
    spec = "local@2:cast:bfloat16/pod@4/global@8:cast:bfloat16"
    topo = HierTopology(2, 1, 2)
    kw = dict(topo=topo, optimizer=sgd(0.05), seed=2,
              eval_batch=cls_task["eval_batch"], per_learner_batch=8)
    bucketed = Simulator(cls_task["loss_fn"], cls_task["init_fn"],
                         cls_task["sample"],
                         hier=HierAvgParams(plan=spec), **kw).run(3)
    perleaf = Simulator(cls_task["loss_fn"], cls_task["init_fn"],
                        cls_task["sample"],
                        hier=HierAvgParams(plan=spec, bucket_bytes=0),
                        **kw).run(3)
    np.testing.assert_array_equal(bucketed.losses, perleaf.losses)
    for a, b in zip(jax.tree.leaves(bucketed.state.params),
                    jax.tree.leaves(perleaf.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bucketed_topk_matches_flat_lax_topk_oracle():
    """Global-k selection: the bucketed topk payload is exactly
    lax.top_k over each learner's whole flattened (f32) model."""
    topo = HierTopology(1, 1, 4)
    key = jax.random.PRNGKey(3)
    tree = {"a": jax.random.normal(key, topo.shape + (9, 3)),
            "b": jax.random.normal(jax.random.fold_in(key, 1),
                                   topo.shape + (17,))}
    red = Bucketed(get_reducer("topk:0.25"))
    st = red.init_state(jax.tree.map(jnp.zeros_like, tree))  # ref=0
    (vals, idx), = red.compress(tree, st)[0]
    n = 9 * 3 + 17
    k = max(1, round(0.25 * n))
    assert vals.shape == (4, k)
    flat = np.concatenate([np.asarray(tree["a"]).reshape(4, -1),
                           np.asarray(tree["b"]).reshape(4, -1)], axis=-1)
    want_vals, want_idx = jax.lax.top_k(jnp.abs(jnp.asarray(flat)), k)
    for r in range(4):
        assert set(np.asarray(idx)[r].tolist()) \
            == set(np.asarray(want_idx)[r].tolist())
        np.testing.assert_allclose(
            np.sort(np.abs(np.asarray(vals)[r])),
            np.sort(np.asarray(want_vals)[r]), rtol=1e-6)


def test_bucketed_topk_3level_plan_trains_with_bucket_space_ef(cls_task):
    """A 3-level plan with stateful EF reducers at two levels trains to
    consensus with per-level EF state carried in bucket space."""
    spec = "local@2:topk:0.5/pod@4/global@8:topk:0.25"
    topo = HierTopology(2, 1, 2)
    h = HierAvgParams(plan=spec)
    plan = h.resolved_plan
    opt = sgd(0.05)
    round_fn = jax.jit(make_hier_round(cls_task["loss_fn"], opt, h))
    state = init_state(topo, cls_task["init_fn"], opt,
                       jax.random.PRNGKey(0), plan=plan)
    # EF state is bucket-space: one ref/err entry per bucket, not per leaf
    n_leaves = len(jax.tree.leaves(state.params))
    for name in ("local", "global"):
        ef = state.comm_state[name]
        assert isinstance(ef, EFState)
        assert len(ef.ref) < n_leaves
        assert all(r.ndim == 4 for r in ef.ref)    # [pods, G, S, n]
    batch = cls_task["sample"](jax.random.PRNGKey(1),
                               h.k2 * topo.n_learners * 8)
    shaped = jax.tree.map(
        lambda x: x.reshape(h.batch_dims + topo.shape + (8,)
                            + x.shape[1:]), batch)
    state, metrics = round_fn(state, shaped)
    assert np.isfinite(float(metrics["loss"]))
    for leaf in jax.tree.leaves(state.params):
        flat = leaf.reshape((topo.n_learners,) + leaf.shape[3:])
        assert bool(jnp.allclose(flat, flat[0:1], atol=1e-6))


def test_layout_checked_init_rejects_mismatched_state(cls_task):
    """Carrying per-leaf (or differently-bucketed) EF state into a
    bucketed round fails loudly, not by silent misalignment."""
    topo = HierTopology(1, 2, 2)
    h = HierAvgParams(k1=2, k2=4, reducer="topk:0.25")
    opt = sgd(0.05)
    round_fn = jax.jit(make_hier_round(cls_task["loss_fn"], opt, h))
    # state built for the PER-LEAF pipeline (bucket_bytes=0)
    bad = init_state(topo, cls_task["init_fn"], opt, jax.random.PRNGKey(0),
                     plan=resolve_plan(
                         HierAvgParams(k1=2, k2=4, reducer="topk:0.25",
                                       bucket_bytes=0)))
    batch = cls_task["sample"](jax.random.PRNGKey(1),
                               h.k2 * topo.n_learners * 8)
    shaped = jax.tree.map(
        lambda x: x.reshape((h.beta, h.k1) + topo.shape + (8,)
                            + x.shape[1:]), batch)
    with pytest.raises((ValueError, TypeError)):
        round_fn(bad, shaped)


def test_explicit_bucketed_modifier_inherits_config_cap():
    """A ':bucketed' spec modifier honors HierAvgParams.bucket_bytes (the
    wrapper's cap is 'inherit' until plan resolution re-caps it)."""
    h = HierAvgParams(k1=2, k2=4, reducer="topk:0.05:bucketed",
                      bucket_bytes=64)
    for lvl in resolve_plan(h).levels:
        assert isinstance(lvl.reducer, Bucketed)
        assert lvl.reducer.effective_bucket_bytes == 64
    # with auto-bucketing off, the explicit marker stays at the default
    h0 = HierAvgParams(k1=2, k2=4, reducer="topk:0.05:bucketed",
                       bucket_bytes=0)
    for lvl in resolve_plan(h0).levels:
        assert isinstance(lvl.reducer, Bucketed)
        assert lvl.reducer.effective_bucket_bytes == 4 << 20


def test_init_state_spec_string_plan_matches_default_round(cls_task):
    """init_state(plan=<spec string>) applies the same default bucketing
    resolve_plan does, so a round built from a default HierAvgParams
    accepts the state; bucket_bytes=0 rebuilds the per-leaf state."""
    spec = "local@2:topk:0.5/global@4:topk:0.25"
    topo = HierTopology(1, 2, 2)
    h = HierAvgParams(plan=spec)
    opt = sgd(0.05)
    round_fn = jax.jit(make_hier_round(cls_task["loss_fn"], opt, h))
    state = init_state(topo, cls_task["init_fn"], opt,
                       jax.random.PRNGKey(0), plan=spec)
    batch = cls_task["sample"](jax.random.PRNGKey(1),
                               h.k2 * topo.n_learners * 8)
    shaped = jax.tree.map(
        lambda x: x.reshape(h.batch_dims + topo.shape + (8,)
                            + x.shape[1:]), batch)
    state, metrics = round_fn(state, shaped)
    assert np.isfinite(float(metrics["loss"]))
    # explicit override routes to the per-leaf layout
    perleaf = init_state(topo, cls_task["init_fn"], opt,
                         jax.random.PRNGKey(0), plan=spec, bucket_bytes=0)
    n_leaves = len(jax.tree.leaves(perleaf.params))
    assert len(jax.tree.leaves(perleaf.comm_state["global"].ref)) \
        == n_leaves
    assert len(jax.tree.leaves(state.comm_state["global"].ref)) < n_leaves


# ----------------------- pipelined bucket schedule --------------------- #

def test_uniform_layout_pads_groups_and_roundtrips():
    """uniform=True (the pipelined engine's layout) pads every bucket of
    a multi-bucket dtype group to the group max; single-bucket groups
    keep their exact size; pack/unpack still round-trips."""
    tree = _mixed_tree()
    lay = BucketLayout.build(tree, bucket_bytes=64, uniform=True)
    by_dtype = {}
    for b in lay.buckets:
        by_dtype.setdefault(b.dtype, []).append(b)
    for dtype, group in by_dtype.items():
        if len(group) > 1:
            assert len({b.shape for b in group}) == 1     # rectangular
            assert all(b.padded_size >= b.size for b in group)
    back = lay.unpack(lay.pack(tree))
    for k in tree:
        assert back[k].dtype == tree[k].dtype
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(tree[k]))
    # ragged and uniform layouts agree when every group has one bucket
    big = BucketLayout.build(tree, uniform=True)
    assert [b.shape for b in big.buckets] \
        == [b.shape for b in BucketLayout.build(tree).buckets]


def test_matrix_uniform_layout_common_panel_and_roundtrips():
    """matrix+uniform (the pipelined PowerSGD layout, previously
    refused): every bucket of a multi-bucket group pads to the
    elementwise-max common panel shape, so the scan's stacked stages are
    rectangular; pack/unpack still round-trips bit-exactly."""
    tree = _mixed_tree()
    lay = BucketLayout.build(tree, bucket_bytes=64, matrix=True,
                             uniform=True)
    by_dtype = {}
    for b in lay.buckets:
        assert len(b.shape) == 2
        by_dtype.setdefault(b.dtype, []).append(b)
    for group in by_dtype.values():
        if len(group) > 1:
            assert len({b.shape for b in group}) == 1
            assert all(b.padded_size >= b.size for b in group)
    assert any(len(g) > 1 for g in by_dtype.values())  # really exercised
    back = lay.unpack(lay.pack(tree))
    for k in tree:
        assert back[k].dtype == tree[k].dtype
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(tree[k]))


def _abstract_shard_plan(F=2):
    """ShardPlan over an AbstractMesh — layout resolution needs only the
    mesh axis sizes, so layout unit tests run without multiple devices."""
    from jax.sharding import AbstractMesh

    from repro.parallel.sharding import ShardPlan
    mesh = AbstractMesh((1, 2, 2, F, 1),
                        ("pod", "group", "local", "fsdp", "model"))
    return ShardPlan(mesh=mesh)


def test_shard_aware_layout_packs_per_shard_runs():
    """fsdp>1 layouts pack sharded leaves into per-shard runs (wire view
    [*lead, F, run]), pad every run to a multiple of the learner count
    (so each level's reduce-scatter tiles), and round-trip pack/unpack
    bit-exactly."""
    tree = _mixed_tree()
    sp = _abstract_shard_plan()
    lay = BucketLayout.build(tree, shards=sp)
    sharded = {b.dtype: b for b in lay.buckets if b.shards > 1}
    flat = {b.dtype: b for b in lay.buckets if b.shards == 1}
    # rank>=2 leaves shard trailing dim 0 over fsdp (DEFAULT_RULES
    # fallback); w0 [6,5] and w1 [8,3] divide F=2, h [3,4,2] does not
    # (3 % 2) and stays flat — the safe_pspec drop, mirrored exactly
    assert sharded["float32"].size == 6 * 5 // 2
    assert sharded["bfloat16"].size == 8 * 3 // 2
    assert flat["bfloat16"].size == 3 * 4 * 2
    for b in lay.buckets:
        assert b.shape[-1] % sp.n_lead == 0
    # wire view: per-shard run 15 padded to 16, F-major axis explicit
    assert sharded["float32"].shape == (2, 16)
    back = lay.unpack(lay.pack(tree))
    for k in tree:
        assert back[k].dtype == tree[k].dtype
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(tree[k]))
    # codec view merges shards into the local-learner axis (shard space)
    packed = lay.pack(tree)
    codec = lay.codec_view(packed)
    for b, w, c in zip(lay.buckets, packed, codec):
        if b.shards > 1:
            assert w.shape[:3] == (1, 2, 2) and c.shape[:3] == (1, 2, 4)
        np.testing.assert_array_equal(
            np.asarray(lay._to_wire(b, c)), np.asarray(w))


def test_matrix_mode_refuses_sharded_leaves():
    """Low-rank (matrix-mode) reducers cannot act on a per-shard run:
    building a matrix layout under an fsdp>1 ShardPlan refuses loudly,
    naming the offending leaf; fsdp=1 stays byte-identical."""
    tree = _mixed_tree()
    with pytest.raises(NotImplementedError, match="fsdp"):
        BucketLayout.build(tree, matrix=True, shards=_abstract_shard_plan())
    lay = BucketLayout.build(tree, shards=None)
    assert lay.n_leaves == 5
    assert [b.shape for b in lay.buckets] \
        == [b.shape for b in BucketLayout.build(tree).buckets]


def test_contradictory_schedule_modifiers_raise():
    with pytest.raises(ValueError, match="contradictory"):
        get_reducer("topk:0.05:perleaf:pipelined")
    with pytest.raises(ValueError, match="contradictory"):
        get_reducer("topk:0.05:pipelined:serial")


@pytest.mark.parametrize("spec", ["mean", "cast:bfloat16"])
def test_pipelined_bit_identical_to_serial_single_reduction(spec):
    """Pipelining is a schedule change only: multi-bucket mean/cast
    reductions are bit-identical serial vs pipelined over a pair, and
    agree to summation order over four learners."""
    wire = jnp.bfloat16 if spec.startswith("cast") else jnp.float32
    for topo in (PAIR, TOPO):
        tree = _mixed_tree(topo)
        ser, _ = reduce_with(Bucketed(get_reducer(spec), 64),
                             global_average, tree, ())
        pip, _ = reduce_with(Pipelined(get_reducer(spec), 64),
                             global_average, tree, ())
        for k in tree:
            _assert_same_mean(pip[k], ser[k], tree[k], topo, wire)


def test_pipelined_cast_trajectory_bit_identical_to_serial(cls_task):
    """Full-trajectory bit-exactness: a 3-level cast plan trained with
    overlap on vs off (multi-bucket: tiny cap) gives byte-identical
    params — pipelining must not change math."""
    spec = "local@2:cast:bfloat16/pod@4/global@8:cast:bfloat16"
    topo = HierTopology(2, 1, 2)
    kw = dict(topo=topo, optimizer=sgd(0.05), seed=2,
              eval_batch=cls_task["eval_batch"], per_learner_batch=8)
    piped = Simulator(cls_task["loss_fn"], cls_task["init_fn"],
                      cls_task["sample"],
                      hier=HierAvgParams(plan=spec, bucket_bytes=256,
                                         overlap=True), **kw).run(3)
    serial = Simulator(cls_task["loss_fn"], cls_task["init_fn"],
                       cls_task["sample"],
                       hier=HierAvgParams(plan=spec, bucket_bytes=256,
                                          overlap=False), **kw).run(3)
    np.testing.assert_array_equal(piped.losses, serial.losses)
    for a, b in zip(jax.tree.leaves(piped.state.params),
                    jax.tree.leaves(serial.state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _run_steps(step_fn, state, shaped, k2):
    flat = jax.tree.map(lambda x: x.reshape((k2,) + x.shape[2:]), shaped)
    for t in range(k2):
        state, _ = step_fn(state, jax.tree.map(lambda x: x[t], flat))
    return state


@pytest.mark.parametrize("spec", ["mean:bucketed", "cast:bfloat16"])
def test_pipelined_step_api_bit_identical_to_serial(cls_task, spec):
    """Per-API bit-exactness: the step-wise (lax.cond-masked) API under
    the pipelined schedule == the same API under the serial schedule,
    for mean/cast at a multi-bucket cap.  Pipelining must not change
    math in either API.  (``mean:bucketed`` — not ``:pipelined``, which
    would pin the engine and defeat the overlap toggle — resolves to
    Pipelined with overlap=True and plain Bucketed with overlap=False.)"""
    topo = HierTopology(1, 2, 2)
    states, params = {}, {}
    for overlap in (True, False):
        h = HierAvgParams(k1=2, k2=4, reducer=spec, bucket_bytes=256,
                          overlap=overlap)
        opt = sgd(0.05)
        step_fn = jax.jit(make_hier_step(cls_task["loss_fn"], opt, h))
        s = init_state(topo, cls_task["init_fn"], opt,
                       jax.random.PRNGKey(0), plan=h.resolved_plan)
        batch = cls_task["sample"](jax.random.PRNGKey(1),
                                   h.k2 * topo.n_learners * 8)
        shaped = shard_round_batch(batch, h, topo)
        params[overlap] = _run_steps(step_fn, s, shaped, h.k2).params
    for a, b in zip(jax.tree.leaves(params[True]),
                    jax.tree.leaves(params[False])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pipelined_step_api_matches_round_api_mean(cls_task):
    """Step-wise counter masking and the scan-nest round agree for the
    pipelined bucketed mean.  Across APIs the round program also runs
    the (subsumed) local mean at the outer boundary — a float
    reassociation of the same average, so the cross-API comparison is
    allclose at fp32 resolution; bit-exactness is asserted WITHIN each
    API by test_pipelined_step_api_bit_identical_to_serial and the
    trajectory test above (pipelining itself changes nothing)."""
    topo = HierTopology(1, 2, 2)
    h = HierAvgParams(k1=2, k2=4, reducer="mean:pipelined",
                      bucket_bytes=256)
    opt = sgd(0.05)
    round_fn = jax.jit(make_hier_round(cls_task["loss_fn"], opt, h))
    step_fn = jax.jit(make_hier_step(cls_task["loss_fn"], opt, h))
    key = jax.random.PRNGKey(0)
    s_round = init_state(topo, cls_task["init_fn"], opt, key,
                         plan=h.resolved_plan)
    s_step = init_state(topo, cls_task["init_fn"], opt, key,
                        plan=h.resolved_plan)
    batch = cls_task["sample"](jax.random.PRNGKey(1),
                               h.k2 * topo.n_learners * 8)
    shaped = shard_round_batch(batch, h, topo)
    s_round, _ = round_fn(s_round, shaped)
    s_step = _run_steps(step_fn, s_step, shaped, h.k2)
    for a, b in zip(jax.tree.leaves(s_round.params),
                    jax.tree.leaves(s_step.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-6, atol=1e-6)


def test_pipelined_topk_multibucket_trains_with_uniform_ef(cls_task):
    """A 2-level plan with EF topk at both levels, forced multi-bucket
    (tiny cap): the pipelined engine trains to consensus and carries
    uniform (padded) bucket-space EF state."""
    spec = "local@2:topk:0.5/global@4:topk:0.25"
    topo = HierTopology(1, 2, 2)
    h = HierAvgParams(plan=spec, bucket_bytes=256)
    plan = h.resolved_plan
    assert all(isinstance(l.reducer, Pipelined) for l in plan.levels)
    opt = sgd(0.05)
    round_fn = jax.jit(make_hier_round(cls_task["loss_fn"], opt, h))
    state = init_state(topo, cls_task["init_fn"], opt,
                       jax.random.PRNGKey(0), plan=plan)
    # multi-bucket, uniform within the f32 group
    ef = state.comm_state["global"]
    assert len(ef.ref) > 1
    assert len({r.shape for r in ef.ref}) == 1
    batch = cls_task["sample"](jax.random.PRNGKey(1),
                               h.k2 * topo.n_learners * 8)
    shaped = shard_round_batch(batch, h, topo)
    state, metrics = round_fn(state, shaped)
    assert np.isfinite(float(metrics["loss"]))
    for leaf in jax.tree.leaves(state.params):
        flat = leaf.reshape((topo.n_learners,) + leaf.shape[3:])
        assert bool(jnp.allclose(flat, flat[0:1], atol=1e-6))
    # a second round accepts the carried state (structure is stable)
    state, metrics = round_fn(state, shaped)
    assert np.isfinite(float(metrics["loss"]))


def test_pipelined_overlap_mismatched_state_fails_loudly(cls_task):
    """Serial-schedule EF state into a pipelined multi-bucket round (or
    vice versa) trips the layout check, not silent misalignment."""
    topo = HierTopology(1, 2, 2)
    h = HierAvgParams(k1=2, k2=4, reducer="topk:0.25", bucket_bytes=72)
    opt = sgd(0.05)
    round_fn = jax.jit(make_hier_round(cls_task["loss_fn"], opt, h))
    bad = init_state(topo, cls_task["init_fn"], opt, jax.random.PRNGKey(0),
                     plan=resolve_plan(HierAvgParams(
                         k1=2, k2=4, reducer="topk:0.25", bucket_bytes=72,
                         overlap=False)))
    batch = cls_task["sample"](jax.random.PRNGKey(1),
                               h.k2 * topo.n_learners * 8)
    shaped = shard_round_batch(batch, h, topo)
    with pytest.raises((ValueError, TypeError)):
        round_fn(bad, shaped)


def test_overlap_false_demotes_auto_pipelined_plan(cls_task):
    """The init_state escape hatch: re-resolving an already-pipelined
    (auto, not ':pipelined'-pinned) plan with overlap=False demotes it
    to the serial engine, so the state it builds matches a serial round
    (regression: auto Pipelined wrappers were treated as explicit pins
    and kept their uniform-padded layout)."""
    from repro.core.plan import apply_bucketing
    # resolved with overlap default on -> auto-Pipelined levels (cap 72)
    h = HierAvgParams(k1=2, k2=4, reducer="topk:0.25", bucket_bytes=72)
    resolved = resolve_plan(h)
    assert all(isinstance(l.reducer, Pipelined) for l in resolved.levels)
    demoted = apply_bucketing(resolved, 72, overlap=False)
    assert all(type(l.reducer) is Bucketed for l in demoted.levels)
    # ... while an explicit ':pipelined' pin survives the demotion
    pinned = resolve_plan(HierAvgParams(
        k1=2, k2=4, reducer="topk:0.25:pipelined", bucket_bytes=72))
    assert all(isinstance(l.reducer, Pipelined)
               for l in apply_bucketing(pinned, 72, overlap=False).levels)
    # end to end: state built from the PIPELINED instance with
    # overlap=False runs in a serial overlap=False round
    topo = HierTopology(1, 2, 2)
    hs = HierAvgParams(k1=2, k2=4, reducer="topk:0.25", bucket_bytes=72,
                       overlap=False)
    opt = sgd(0.05)
    round_fn = jax.jit(make_hier_round(cls_task["loss_fn"], opt, hs))
    state = init_state(topo, cls_task["init_fn"], opt,
                       jax.random.PRNGKey(0), plan=resolved,
                       bucket_bytes=72, overlap=False)
    batch = cls_task["sample"](jax.random.PRNGKey(1),
                               hs.k2 * topo.n_learners * 8)
    shaped = shard_round_batch(batch, hs, topo)
    state, metrics = round_fn(state, shaped)
    assert np.isfinite(float(metrics["loss"]))


def test_pipelined_qint8_reduces_within_quant_error():
    """Stateless quantizing codec through the pipeline: the uniform
    padding shifts qint8's block boundaries vs the ragged serial layout
    (so no bit-exactness claim), but the reduction must still land
    within the codec's per-block error bound of the true mean."""
    tree = _mixed_tree()
    dense, _ = reduce_with(get_reducer("mean"), global_average, tree, ())
    pip, _ = reduce_with(Pipelined(get_reducer("qint8:32"), 64),
                         global_average, tree, ())
    for k in tree:
        a = np.asarray(pip[k], np.float32)
        b = np.asarray(dense[k], np.float32)
        bound = np.abs(np.asarray(tree[k], np.float32)).max() / 100.0
        np.testing.assert_allclose(a, b, atol=max(bound, 0.05))


def test_pipelined_powersgd_bit_identical_to_serial_schedule():
    """PowerSGD rides the pipeline (per-bucket warm-start state splits;
    EF/ref finalized INSIDE the scan): on the same uniform matrix
    layout, the pipelined schedule is bit-identical to the serial one —
    outputs AND the carried state (ref, err, warm-started q).  The
    layouts must match for the claim (ragged vs common-panel padding
    changes the matrix reshape), so the serial leg runs Bucketed.reduce
    unbound on the SAME Pipelined reducer."""
    tree = _mixed_tree()
    f32 = {k: v for k, v in tree.items() if v.dtype == jnp.float32}
    pip_red = Pipelined(get_reducer("powersgd:2"), 64)
    st0 = pip_red.init_state(jax.tree.map(jnp.zeros_like, f32))
    n_b = pip_red.layout_for(f32).n_buckets
    assert n_b >= 2                      # a real multi-stage pipeline
    assert pip_red.inner.split_bucket_states(st0, n_b) is not None
    ser, ser_st = Bucketed.reduce(pip_red, global_average, f32, st0)
    pip, pip_st = reduce_with(pip_red, global_average, f32, st0)
    for k in f32:
        np.testing.assert_array_equal(np.asarray(pip[k]),
                                      np.asarray(ser[k]))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), pip_st, ser_st)


def test_pipelined_topk_ef_bit_identical_to_serial_schedule():
    """Stateful sparse EF codec through the finalize-in-scan path: same
    uniform layout, serial vs pipelined schedules agree bitwise on
    outputs AND the carried EF state (residual, reference) — the EF
    update must not see stale or re-materialized references when it
    moves inside the scan body."""
    tree = _mixed_tree()
    f32 = {k: v for k, v in tree.items() if v.dtype == jnp.float32}
    pip_red = Pipelined(get_reducer("topk:0.3"), 64)
    st0 = pip_red.init_state(jax.tree.map(jnp.zeros_like, f32))
    assert pip_red.layout_for(f32).n_buckets >= 2
    ser, ser_st = Bucketed.reduce(pip_red, global_average, f32, st0)
    pip, pip_st = reduce_with(pip_red, global_average, f32, st0)
    for k in f32:
        np.testing.assert_array_equal(np.asarray(pip[k]),
                                      np.asarray(ser[k]))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), pip_st, ser_st)
    # and the EF state is genuinely non-trivial (the codec dropped mass)
    assert any(float(jnp.max(jnp.abs(x))) > 0
               for x in jax.tree.leaves(pip_st)
               if jnp.issubdtype(x.dtype, jnp.floating))


# ------------------------------ accounting ---------------------------- #

def test_bucketed_payload_and_message_accounting():
    tree = {"w": jnp.zeros((100, 10)), "b": jnp.zeros((10,)),
            "v": jnp.zeros((77,))}
    dense = get_reducer("mean")
    assert dense.n_messages(tree) == 3
    bucketed_cast = Bucketed(get_reducer("cast:bfloat16"))
    # one f32 bucket -> one collective; payload bytes unchanged vs per-leaf
    assert bucketed_cast.n_messages(tree) == 1
    assert bucketed_cast.payload_bytes(tree) \
        == get_reducer("cast:bfloat16").payload_bytes(tree)
    # global k: one k of the whole model, not one per leaf
    topk = Bucketed(get_reducer("topk:0.1"))
    n = 100 * 10 + 10 + 77
    assert topk.payload_bytes(tree) == max(1, round(0.1 * n)) * 8
    # fused qint8 ships ONE packed buffer per bucket; the twopass
    # baseline bills the int8 payload and the fp32 scales separately
    assert Bucketed(get_reducer("qint8:128")).n_messages(tree) == 1
    assert Bucketed(get_reducer("qint8:128:twopass")).n_messages(tree) == 2
    assert get_reducer("qint8:128").n_messages(tree) == 3
    assert get_reducer("qint8:128:twopass").n_messages(tree) == 6
    # powersgd: two factor messages per compressible matrix bucket;
    # un-bucketed, two for the compressible w plus one each for the
    # dense-fallback 1-D b and v
    assert Bucketed(get_reducer("powersgd:2")).n_messages(tree) == 2
    assert get_reducer("powersgd:2").n_messages(tree) == 4


def test_plan_comm_costing_bills_messages():
    from repro.core.theory import CommModel, plan_comm_per_round
    tree = {"w": jax.ShapeDtypeStruct((100, 10), jnp.float32),
            "b": jax.ShapeDtypeStruct((10,), jnp.float32)}
    topo = HierTopology(1, 2, 4)
    cm = CommModel()
    per_leaf = plan_comm_per_round(
        resolve_plan(HierAvgParams(k1=2, k2=4, reducer="qint8:128",
                                   bucket_bytes=0)), topo, tree, cm)
    bucketed = plan_comm_per_round(
        resolve_plan(HierAvgParams(k1=2, k2=4, reducer="qint8:128")),
        topo, tree, cm)
    assert per_leaf[0].messages == 2 and bucketed[0].messages == 1
    # no more wire bytes (packing saves partial qint8 blocks), strictly
    # less startup latency
    for pl, bk in zip(per_leaf, bucketed):
        assert bk.payload_bytes <= pl.payload_bytes
        assert bk.seconds_per_round < pl.seconds_per_round
