"""The round's device-side scopes (``telemetry/spans.py``): every matmul
of a training step, forward and backward, runs under a layer's scope,
and each plan level's reduction under its own.

The step is traced as ``train.main`` builds it, for the reduced widths
of one architecture of each decoder family the benchmark runs (dense,
vlm, hybrid).  A name stack is relative to the jaxpr it sits in, so an
equation's full path joins the stacks of the equations that enclose it,
as the compiler does for the ``op_name`` it hands the device trace.
"""
from __future__ import annotations

import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest
from jax.extend import core as jcore

from repro.telemetry.spans import REDUCE, SCOPES

ROOT = Path(__file__).resolve().parents[1]
TOKEN = re.compile(r"(?:^|[/(])(attention|mlp|ssm|head_loss|reduce\.[\w-]+)"
                   r"(?=[/)]|$)")
TINY = ("--reduced", "--learners", "2", "--s", "2", "--batch", "1",
        "--seq", "64", "--rounds", "1", "--no-overlap")


def _subjaxprs(params):
    for v in params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, jcore.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jcore.Jaxpr):
                yield x


def _paths(jaxpr, prefix=""):
    """(primitive, full name-stack path) of every equation, nested
    jaxprs included."""
    for eqn in jaxpr.eqns:
        path = f"{prefix}/{eqn.source_info.name_stack}"
        yield eqn.primitive.name, path
        for sub in _subjaxprs(eqn.params):
            yield from _paths(sub, path)


def _round_paths(arch, monkeypatch, tmp_path, plan="local@1/global@4"):
    from repro.launch import train
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    run = train.main(["--arch", arch, "--plan", plan, *TINY])
    spec = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        (run.state, run.batch))
    return list(_paths(jax.make_jaxpr(run.round_fn)(*spec).jaxpr))


def test_scope_names_are_the_tokens_the_trace_reader_matches():
    assert SCOPES == ("attention", "mlp", "ssm", "head_loss")
    for name in SCOPES + (f"{REDUCE}.local",):
        assert TOKEN.findall(f"jit(f)/while/body/{name}/dot_general") \
            == [name]
        assert TOKEN.findall(f"jit(f)/transpose(jvp({name}))/mul") == [name]


@pytest.mark.parametrize("arch,scopes", [
    ("yi-34b", {"attention", "mlp", "head_loss"}),
    ("qwen2-vl-2b", {"attention", "mlp", "head_loss"}),
    ("hymba-1.5b", {"attention", "mlp", "ssm", "head_loss"}),
])
def test_every_matmul_of_the_step_is_scoped(arch, scopes, monkeypatch,
                                            tmp_path):
    dots = [p for prim, p in _round_paths(arch, monkeypatch, tmp_path)
            if prim == "dot_general"]
    assert dots
    unscoped = [p for p in dots if not TOKEN.search(p)]
    assert not unscoped, unscoped[:5]
    innermost = {TOKEN.findall(p)[-1] for p in dots}
    assert innermost == scopes
    backward = {TOKEN.findall(p)[-1] for p in dots if "transpose(" in p}
    assert backward == scopes


def test_the_rest_of_the_step_is_left_unscoped(monkeypatch, tmp_path):
    """The embedding lookup (a gather; the loss's gather of the labels'
    logits is the head's) carries no scope, and no scope but the five
    is opened."""
    paths = _round_paths("qwen2-vl-2b", monkeypatch, tmp_path)
    gathers = [p for prim, p in paths if prim == "gather"]
    assert any(not TOKEN.search(p) for p in gathers)
    assert any("head_loss" in p for p in gathers)
    scoped = {TOKEN.findall(p)[-1] for _, p in paths if TOKEN.search(p)}
    assert scoped == {"attention", "mlp", "head_loss",
                      "reduce.local", "reduce.global"}


def test_each_level_reduces_under_its_scope_on_four_devices(tmp_path):
    """One learner per device: the compiled round's all-reduces are the
    plan levels' grouped means, each under ``reduce.<level>``."""
    code = textwrap.dedent("""
        import re
        import jax
        from repro.launch import train
        run = train.main(["--arch", "qwen2-vl-2b", "--reduced",
                          "--learners", "4", "--s", "2",
                          "--plan", "local@1:qint8:128/global@2",
                          "--batch", "1", "--seq", "32", "--rounds", "1",
                          "--no-overlap"])
        spec = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            (run.state, run.batch))
        hlo = run.round_fn.lower(*spec).compile().as_text()
        for line in hlo.splitlines():
            if re.search(r" all-reduce(-start)?\\(", line):
                m = re.search(r'op_name="([^"]*)"', line)
                print("ALLREDUCE", m.group(1) if m else "")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, cwd=ROOT, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    names = [ln.split(" ", 1)[1] for ln in out.stdout.splitlines()
             if ln.startswith("ALLREDUCE ")]
    assert "mesh={'pod': 1, 'group': 2, 'local': 2" in out.stdout
    levels = {TOKEN.findall(n)[-1] if TOKEN.search(n) else None
              for n in names}
    assert {"reduce.local", "reduce.global"} <= levels


@pytest.mark.parametrize("elastic", [False, True])
def test_the_step_api_reduces_each_level_under_its_scope(cls_task, elastic):
    """``make_hier_step``: each level's reduction, inside its ``cond``
    branch, under ``reduce.<level>``; the SGD step under none."""
    import jax.numpy as jnp
    from repro.configs.base import HierAvgParams
    from repro.core import HierTopology, init_state, make_hier_step
    from repro.optim import sgd
    topo = HierTopology(2, 1, 2)
    h = HierAvgParams(plan="local@2/pod@4/global@8")
    opt = sgd(0.05)
    state = init_state(topo, cls_task["init_fn"], opt, jax.random.PRNGKey(3))
    batch = jax.tree.map(lambda x: x.reshape(topo.shape + (4,) + x.shape[1:]),
                         cls_task["sample"](jax.random.PRNGKey(4),
                                            topo.n_learners * 4))
    args = (state, batch)
    if elastic:
        args += (jnp.ones((3,) + topo.shape, bool),)
    step = make_hier_step(cls_task["loss_fn"], opt, h, elastic=elastic)
    paths = list(_paths(jax.make_jaxpr(step)(*args).jaxpr))
    scoped = {TOKEN.findall(p)[-1] for _, p in paths if TOKEN.search(p)}
    assert scoped == {"reduce.local", "reduce.pod", "reduce.global"}
    dots = [p for prim, p in paths if prim == "dot_general"]
    assert dots and not any(TOKEN.search(p) for p in dots)


@pytest.mark.parametrize("remat", [False, True])
def test_fused_attention_kernels_run_under_the_attention_scope(remat):
    """The fused kernel's forward, dq and dk/dv calls and the XLA work
    around them (head-major copies, the backward's row sums) sit under
    ``attention``: its ``custom_vjp`` backward is traced apart from the
    forward, inside the transpose of the caller's scope."""
    import jax.numpy as jnp
    from repro.models.attention import gqa_attention, gqa_init
    p = gqa_init(jax.random.PRNGKey(0), 128, 4, 2, 64)
    x = jnp.ones((1, 256, 128))

    def loss(p, x):
        out = gqa_attention(p, x, None, None, n_heads=4, n_kv_heads=2,
                            head_dim=64, window=100, impl="pallas")
        with jax.named_scope("head_loss"):
            return out.sum()

    fn = jax.grad(jax.checkpoint(loss) if remat else loss)
    paths = list(_paths(jax.make_jaxpr(fn)(p, x).jaxpr))
    kernels = sorted(path.rsplit("/", 1)[1] for prim, path in paths
                     if prim == "pallas_call")
    assert kernels == ["flash_attention_dkv", "flash_attention_dq"] + \
        ["flash_attention_fwd"] * (2 if remat else 1)
    work = [path for prim, path in paths
            if prim in ("pallas_call", "transpose", "reduce_sum",
                        "dot_general")]
    unscoped = [w for w in work if not TOKEN.search(w)]
    assert not unscoped, unscoped[:5]
