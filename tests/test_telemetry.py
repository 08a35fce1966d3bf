"""Telemetry plane: metrics rows, span traces, device-side gradstats.

Fast tier covers the host pieces (MetricsLogger schema contract, JSONL
round-trip, Chrome-trace nesting, gradstats vs numpy oracles, the
CostAwarePlan.observe signal path) and the in-process bit-identity of
the telemetry-on round on the serial and pipelined engines.  The slow
tier adds the fsdp=2 subprocess bit-identity leg and the serving-engine
telemetry rows on a real (reduced) arch.
"""
import glob
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.autotune import CostAwarePlan
from repro.configs.base import HierAvgParams
from repro.core import HierTopology, Simulator
from repro.telemetry import (ROW_SCHEMAS, SCHEMA_VERSION, MetricsLogger,
                             SpanTracer, TelemetryConfig, codec_error,
                             ef_mass, group_divergence, resolve_telemetry,
                             validate_jsonl)

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

TOPO = HierTopology(2, 2, 2)
PLAN = "local@2/pod@4/global@8:topk:0.25"


# ------------------------------------------------------------------- #
# MetricsLogger: channels, rows, schema contract, JSONL round-trip

def test_typed_channels_snapshot():
    m = MetricsLogger()
    m.count("rounds")
    m.count("rounds", 2)
    m.gauge("pages_in_use", 7)
    for v in (1.0, 2.0, 3.0, 10.0):
        m.histogram("wall", v)
    snap = m.snapshot()
    assert snap["counters"]["rounds"] == 3
    assert snap["gauges"]["pages_in_use"] == 7.0
    h = snap["histograms"]["wall"]
    assert h["count"] == 4 and h["min"] == 1.0 and h["max"] == 10.0


def test_row_schema_golden_keys():
    """The frozen per-subsystem REQUIRED key sets — the compatibility
    contract downstream readers (CI JSONL smoke, CostAwarePlan.observe)
    rely on.  Changing these sets must bump SCHEMA_VERSION; this test is
    the tripwire."""
    assert SCHEMA_VERSION == 1
    assert ROW_SCHEMAS["train_round"] == frozenset({
        "schema_version", "subsystem", "round", "loss", "wall_s"})
    assert ROW_SCHEMAS["serve_step"] == frozenset({
        "schema_version", "subsystem", "step", "active_slots",
        "occupancy", "new_tokens", "pages_in_use"})
    assert ROW_SCHEMAS["serve_summary"] == frozenset({
        "schema_version", "subsystem", "engine", "requests", "tokens",
        "decode_steps", "wall_s", "tokens_per_s", "wasted_ratio",
        "refill_events", "peak_pages_in_use"})


def test_log_row_stamps_and_validates():
    m = MetricsLogger()
    row = m.log_row("train_round", round=0, loss=1.5, wall_s=0.01)
    assert row["schema_version"] == SCHEMA_VERSION
    assert row["subsystem"] == "train_round"
    with pytest.raises(ValueError, match="unknown telemetry subsystem"):
        m.log_row("nope", x=1)
    with pytest.raises(ValueError, match="missing required keys"):
        m.log_row("train_round", round=0)        # no loss / wall_s


def test_ring_buffer_and_subsystem_filter():
    m = MetricsLogger(ring=4)
    for r in range(6):
        m.log_row("train_round", round=r, loss=0.0, wall_s=0.0)
    m.log_row("serve_summary", engine="dense", requests=1, tokens=2,
              decode_steps=1, wall_s=0.1, tokens_per_s=20.0,
              wasted_ratio=0.0, refill_events=0, peak_pages_in_use=0)
    rounds = [r["round"] for r in m.rows("train_round")]
    assert rounds == [3, 4, 5]                   # oldest evicted
    assert len(list(m.rows("serve_summary"))) == 1


def test_jsonl_roundtrip_and_validation(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with MetricsLogger(path, flush_every=2) as m:
        m.log_row("train_round", round=0, loss=float("nan"), wall_s=0.01,
                  extra=np.float32(3.0))
        m.log_row("train_round", round=1, loss=0.5, wall_s=0.01)
    rows = validate_jsonl(path)
    assert [r["round"] for r in rows] == [0, 1]
    assert rows[0]["loss"] is None               # nan -> null, strict JSON
    assert rows[0]["extra"] == 3.0               # numpy unwrapped

    bad = str(tmp_path / "bad.jsonl")
    with open(bad, "w") as f:
        f.write(json.dumps({"subsystem": "train_round",
                            "schema_version": SCHEMA_VERSION,
                            "round": 0}) + "\n")
    with pytest.raises(ValueError, match="missing"):
        validate_jsonl(bad)


# ------------------------------------------------------------------- #
# SpanTracer: Chrome-trace export round-trip, nesting

def test_chrome_trace_roundtrips_and_nests(tmp_path):
    tracer = SpanTracer()
    f = jax.jit(lambda x: (x * x).sum())
    x = jnp.ones((8, 8))
    for r in range(2):
        with tracer.span(f"round[{r}]"):
            with tracer.span("device", cat="device"):
                tracer.fence(f(x))
            with tracer.span("host_sync"):
                jax.device_get(f(x))
    path = str(tmp_path / "trace.json")
    tracer.export_chrome_trace(path)
    with open(path) as fh:
        doc = json.load(fh)                      # must parse as strict JSON
    events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len(events) == 6                      # 2 x (round + 2)
    assert any(e.get("ph") == "M" for e in doc["traceEvents"])
    rounds = [e for e in events if e["name"].startswith("round")]
    children = [e for e in events if not e["name"].startswith("round")]
    assert len(rounds) == 2
    # timestamps monotonically ordered parent-to-parent, and every child
    # nested inside some parent's [ts, ts+dur] window
    assert rounds[0]["ts"] <= rounds[1]["ts"]
    for c in children:
        assert any(p["ts"] <= c["ts"]
                   and c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1
                   for p in rounds), c
    cats = {e["cat"] for e in events}
    assert cats == {"host", "device"}


def _annotations(directory):
    path = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    return [e.name for p in data.planes if p.name.startswith("/host")
            for ln in p.lines for e in ln.events]


def test_spans_land_in_any_active_profiler_session(tmp_path):
    """Spans are trace annotations whenever a tracer exists: a session the
    caller started records them, with or without the tracer's own, also
    after the caller stopped the tracer's session and started its own."""
    f = jax.jit(lambda x: (x * x).sum())

    def one_round(tracer, r):
        with tracer.span(f"round[{r}]"):
            with tracer.span("device", cat="device"):
                tracer.fence(f(jnp.ones(8)))

    plain = SpanTracer()
    jax.profiler.start_trace(str(tmp_path / "a"))
    one_round(plain, 0)
    jax.profiler.stop_trace()
    names = _annotations(str(tmp_path / "a"))
    assert "round[0]" in names and "device" in names

    own = SpanTracer(profile_dir=str(tmp_path / "own"))
    own.start_profiler()
    jax.profiler.stop_trace()              # the caller ends it ...
    jax.profiler.start_trace(str(tmp_path / "b"))   # ... and opens its own
    one_round(own, 1)
    jax.profiler.stop_trace()
    names = _annotations(str(tmp_path / "b"))
    assert "round[1]" in names and "device" in names


# ------------------------------------------------------------------- #
# gradstats vs numpy oracles

class _Lvl:
    def __init__(self, axes):
        self.axes = axes


def test_group_divergence_matches_numpy():
    rng = np.random.default_rng(0)
    leaf = rng.normal(size=(2, 2, 2, 3, 5)).astype(np.float32)
    params = {"w": jnp.asarray(leaf)}
    for axes in ((2,), (1, 2), (0, 1, 2)):
        got = float(group_divergence(params, axes))
        m = leaf.mean(axis=axes, keepdims=True)
        want = float(np.square(leaf - m).sum(axis=(3, 4)).mean())
        assert got == pytest.approx(want, rel=1e-5)


def test_codec_error_zero_for_exact_mean_positive_for_lossy():
    rng = np.random.default_rng(1)
    pre = rng.normal(size=(1, 1, 4, 6)).astype(np.float32)
    exact = np.broadcast_to(pre.mean(axis=2, keepdims=True), pre.shape)
    zero = float(codec_error({"w": jnp.asarray(exact)},
                             {"w": jnp.asarray(pre)}, (2,)))
    assert zero == pytest.approx(0.0, abs=1e-10)
    lossy = exact + 0.1
    err = float(codec_error({"w": jnp.asarray(lossy)},
                            {"w": jnp.asarray(pre)}, (2,)))
    want = float(np.square(lossy - exact).sum()
                 / (np.square(exact).sum() + 1e-30))
    assert err == pytest.approx(want, rel=1e-5)


def test_ef_mass_reads_err_and_skips_ints():
    class EF:
        err = {"a": jnp.asarray(np.full((2, 3), 2.0, np.float32)),
               "idx": jnp.asarray(np.ones((4,), np.int32))}

    assert float(ef_mass(EF())) == pytest.approx(24.0)   # ints skipped
    # no .err attr: every float leaf counts
    assert float(ef_mass({"x": jnp.asarray(np.ones((5,), np.float32))})
                 ) == pytest.approx(5.0)


def test_resolve_telemetry_knob():
    assert resolve_telemetry(None) is None
    assert resolve_telemetry(False) is None
    assert resolve_telemetry(True) == TelemetryConfig()
    cfg = TelemetryConfig(grad_var=False)
    assert resolve_telemetry(cfg) is cfg
    with pytest.raises(TypeError):
        resolve_telemetry("yes")


# ------------------------------------------------------------------- #
# bit-identity + row logging through the Simulator

def _sim(cls_task, *, telemetry=None, metrics=None, overlap=True):
    hier = HierAvgParams(plan=PLAN, bucket_bytes=1024, overlap=overlap)
    return Simulator(cls_task["loss_fn"], cls_task["init_fn"],
                     cls_task["sample"], topo=TOPO, hier=hier, seed=5,
                     per_learner_batch=8,
                     eval_batch=cls_task["eval_batch"],
                     telemetry=telemetry, metrics=metrics)


@pytest.mark.parametrize("overlap", [False, True],
                         ids=["serial", "pipelined"])
def test_telemetry_on_is_bit_identical(cls_task, overlap):
    """The device-side stats are pure observers: enabling them must not
    move one bit of the trajectory on either bucket schedule."""
    off = _sim(cls_task, overlap=overlap).run(2)
    on = _sim(cls_task, telemetry=True, overlap=overlap).run(2)
    np.testing.assert_array_equal(off.losses, on.losses)
    np.testing.assert_array_equal(off.eval_losses, on.eval_losses)
    assert on.stats and all(k.startswith("telemetry/") for k in on.stats)
    # lossy topk level shows real compression error; mean levels don't
    assert float(np.max(on.stats["telemetry/codec_err/global"])) > 0.0
    assert float(np.max(on.stats["telemetry/codec_err/local"])) == \
        pytest.approx(0.0, abs=1e-9)


def test_simulator_logs_schema_valid_rows(cls_task, tmp_path):
    path = str(tmp_path / "rows.jsonl")
    logger = MetricsLogger(path, flush_every=1)
    res = _sim(cls_task, telemetry=True, metrics=logger).run(3)
    logger.close()
    rows = validate_jsonl(path)
    train = [r for r in rows if r["subsystem"] == "train_round"]
    assert [r["round"] for r in train] == [0, 1, 2]
    assert all(r["wall_s"] > 0 for r in train)
    assert any(k.startswith("telemetry/") for k in train[0])
    assert res.measured_wall_s is not None and len(res.measured_wall_s) == 3
    snap = logger.snapshot()
    assert snap["counters"]["train/rounds"] == 3
    assert snap["histograms"]["train/round_wall_s"]["count"] == 3


def test_costaware_observe_ingests_rows():
    ctl = CostAwarePlan(plan=PLAN, topo=TOPO)
    assert ctl.observed_wall_s is None and ctl.wall_bias() is None
    for w in (9.0, 0.002, 0.003, 0.004):     # compile-round outlier first
        ctl.observe({"wall_s": w,
                     "active_frac": {"global": 0.5, "pod": 1.0}})
    assert ctl.observed_wall_s == pytest.approx(0.004)   # median rides it out
    assert ctl.observed_active_frac["global"] == pytest.approx(0.5)
    assert ctl.observed_active_frac["pod"] == pytest.approx(1.0)
    assert ctl.modeled_round_wall_s > 0.0
    assert ctl.wall_bias() == pytest.approx(
        0.004 / ctl.modeled_round_wall_s)


# ------------------------------------------------------------------- #
# fsdp=2 subprocess bit-identity (slow)

_FSDP_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=16")
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from repro.configs.base import HierAvgParams
from repro.configs.resnet18_cifar import MLPConfig
from repro.core import (HierTopology, init_state, make_hier_round,
                        unstack_first)
from repro.data.synthetic import make_classification_task
from repro.models.resnet import mlp_cls_init, mlp_cls_loss
from repro.optim import sgd
from repro.parallel.sharding import shard_plan

cfg = MLPConfig(in_dim=16, hidden=(32,), n_classes=4)
sample = make_classification_task(16, 4, seed=11, noise=0.5)
loss_fn = lambda p, b: mlp_cls_loss(p, b)
eval_batch = sample(jax.random.PRNGKey(123), 256)
topo = HierTopology(2, 2, 2)
B = 16
h = HierAvgParams(k1=2, k2=8,
                  plan="local@2:mean:bucketed/pod@4:mean:bucketed/"
                       "global@8:mean:bucketed")
opt = sgd(0.05)
mesh = Mesh(np.array(jax.devices()[:16]).reshape(2, 2, 2, 2, 1),
            ("pod", "group", "local", "fsdp", "model"))
shards = shard_plan(mesh)


def run(telemetry):
    rnd = jax.jit(make_hier_round(loss_fn, opt, h, shards=shards,
                                  telemetry=telemetry))
    state = init_state(topo, lambda k: mlp_cls_init(k, cfg), opt,
                       jax.random.PRNGKey(0), plan=h.resolved_plan,
                       shards=shards)
    dims = tuple(h.resolved_plan.batch_dims)
    losses, dk = [], jax.random.PRNGKey(42)
    for r in range(2):
        dk, sk = jax.random.split(dk)
        batch = sample(sk, h.k2 * topo.n_learners * B)
        shaped = jax.tree.map(
            lambda x: x.reshape(dims + topo.shape + (B,) + x.shape[1:]),
            batch)
        state, _ = rnd(state, shaped)
        l, _ = loss_fn(unstack_first(state.params), eval_batch)
        losses.append(float(l))
    return losses


print(json.dumps({"off": run(None), "on": run(True)}))
"""


@pytest.mark.slow
def test_telemetry_bit_identical_at_fsdp2():
    """The observers must also be invisible on the reduce-scatter/
    all-gather sharded engine (fresh 16-host-device subprocess)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(_REPO, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", _FSDP_CHILD], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["off"] == out["on"]


# ------------------------------------------------------------------- #
# serving engine telemetry (slow: builds a reduced real arch)

@pytest.mark.slow
def test_paged_engine_emits_steps_and_summary():
    from repro.configs import get_config
    from repro.models import build
    from repro.serve import GenerationConfig, PagedServeEngine

    cfg = get_config("yi-34b").reduced()
    bundle = build(cfg, cache_dtype=jnp.float32)
    params = bundle.init(jax.random.PRNGKey(0))
    m = MetricsLogger()
    eng = PagedServeEngine(bundle, params, slots=2, page_size=8,
                           max_len=24,
                           gen=GenerationConfig(max_new_tokens=4),
                           metrics=m)
    rng = np.random.default_rng(0)
    reqs = [rng.integers(0, cfg.vocab_size, size=12).astype(np.int32)
            for _ in range(4)]
    results = eng.serve_queue(reqs)
    assert len(results) == 4
    steps = list(m.rows("serve_step"))
    assert steps and all(0 < s["active_slots"] <= 2 for s in steps)
    assert all(s["pages_in_use"] >= 0 for s in steps)
    assert [s["step"] for s in steps] == list(range(len(steps)))
    summary = eng.steady_state_summary()
    logged = list(m.rows("serve_summary"))[-1]
    assert all(logged[k] == v for k, v in summary.items())
    assert summary["engine"] == "paged"
    assert summary["requests"] == 4
    assert summary["peak_pages_in_use"] > 0
    assert summary["refill_events"] >= 2      # 4 reqs through 2 slots
    assert 0.0 < summary["mean_occupancy"] <= 1.0
    assert summary["wasted_ratio"] == 0.0     # token-level refill


@pytest.mark.slow
def test_dense_engine_summary_exposes_wasted_steps():
    from repro.configs import get_config
    from repro.models import build
    from repro.serve import GenerationConfig, ServeEngine

    cfg = get_config("rwkv6-1.6b").reduced()
    bundle = build(cfg, cache_dtype=jnp.float32)
    params = bundle.init(jax.random.PRNGKey(0))
    m = MetricsLogger()
    eng = ServeEngine(bundle, params, max_len=64,
                      gen=GenerationConfig(max_new_tokens=6),
                      metrics=m)
    rng = np.random.default_rng(0)
    reqs = [rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
            for _ in range(3)]
    # per-request budgets below the wave length => provably wasted steps
    eng.serve_queue(reqs, slots=2, max_new=[2, 2, 2])
    s = eng.steady_state_summary()
    assert s["engine"] == "dense" and s["requests"] == 3
    assert s["decode_steps"] == 3 * 5          # full wave scan, always
    assert s["wasted_ratio"] > 0.0
    assert s["refill_events"] == 0 and s["peak_pages_in_use"] == 0
    assert list(m.rows("serve_summary"))       # row logged
