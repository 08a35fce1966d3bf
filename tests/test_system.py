"""End-to-end behaviour tests for the full system."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config

pytestmark = pytest.mark.slow
from repro.configs.base import HierAvgParams
from repro.core import HierTopology, Simulator, unstack_first
from repro.data.synthetic import make_markov_task, markov_lm_batch
from repro.models import build
from repro.optim import sgd

ROOT = os.path.join(os.path.dirname(__file__), "..")
ENV = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))


def test_hier_avg_trains_reduced_lm():
    """Full-stack: Hier-AVG trains a reduced pool arch (hymba) on a Markov
    LM task."""
    cfg = get_config("hymba-1.5b").reduced()
    bundle = build(cfg)
    logits_T, floor = make_markov_task(cfg.vocab_size, temperature=2.0)

    def sample(key, n):
        return markov_lm_batch(key, n, 16, logits_T)

    topo = HierTopology(1, 2, 2)
    sim = Simulator(bundle.loss_fn, bundle.init, sample, topo=topo,
                    hier=HierAvgParams(k1=2, k2=4), optimizer=sgd(0.5),
                    per_learner_batch=4, seed=0,
                    eval_batch=sample(jax.random.PRNGKey(77), 32))
    r = sim.run(6)
    assert r.eval_losses[-1] < r.eval_losses[0] - 0.05
    assert np.isfinite(r.eval_losses).all()


def test_train_driver_cli():
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "rwkv6-1.6b",
         "--reduced", "--rounds", "2", "--k1", "1", "--k2", "2",
         "--learners", "2", "--s", "2", "--batch", "2", "--seq", "16"],
        capture_output=True, text=True, env=ENV, cwd=ROOT, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "round   1" in out.stdout


def test_serve_driver_cli():
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "qwen2-vl-2b", "--reduced", "--requests", "3", "--slots", "2",
         "--prompt-len", "8", "--max-new", "4"],
        capture_output=True, text=True, env=ENV, cwd=ROOT, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "3 requests" in out.stdout


def test_dryrun_cli_one_case(tmp_path):
    """The multi-pod dry-run machinery lowers+compiles a full-size case in a
    fresh process (512 host devices)."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "rwkv6-1.6b", "--shape", "decode_32k", "--out", str(tmp_path)],
        capture_output=True, text=True, env=ENV, cwd=ROOT, timeout=560)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.load(open(tmp_path / "rwkv6-1.6b__decode_32k__1pod.json"))
    assert rec["chips"] == 256
    assert rec["roofline"]["bottleneck"] in ("compute", "memory",
                                             "collective")


def test_checkpoint_resume_training(tmp_path, cls_task):
    """Save averaged model mid-training, restore, continue — the next round
    is identical to continuing without the save/restore."""
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    from repro.core import init_state, make_hier_round, stack_like
    from repro.core.hier_avg import TrainState

    topo = HierTopology(1, 2, 2)
    h = HierAvgParams(k1=2, k2=2)
    opt = sgd(0.05)
    rf = jax.jit(make_hier_round(cls_task["loss_fn"], opt, h))
    state = init_state(topo, cls_task["init_fn"], opt, jax.random.PRNGKey(0))

    def rb(seed):
        b = cls_task["sample"](jax.random.PRNGKey(seed),
                               h.k2 * topo.n_learners * 4)
        return jax.tree.map(
            lambda x: x.reshape((h.beta, h.k1) + topo.shape + (4,)
                                + x.shape[1:]), b)

    state, _ = rf(state, rb(1))
    avg = unstack_first(state.params)
    save_checkpoint(str(tmp_path / "ck"), avg, step=int(state.step))

    restored = restore_checkpoint(str(tmp_path / "ck"),
                                  jax.tree.map(jnp.zeros_like, avg))
    state2 = TrainState(stack_like(topo, restored),
                        opt.init(stack_like(topo, restored)), state.step)
    s_a, m_a = rf(state, rb(2))
    s_b, m_b = rf(state2, rb(2))
    np.testing.assert_allclose(float(m_a["loss"]), float(m_b["loss"]),
                               rtol=1e-5)
