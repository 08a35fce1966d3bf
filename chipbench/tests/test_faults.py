"""The harness past its look for a chip, on tiny cells: a sound run is
correct, and a run whose timed path is broken underneath is not."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from conftest import run_tiny

P1 = "tiny-qwen2-vl-2b.train.p1"
HYMBA = "tiny-hymba-1.5b.train.p1"
HIER = "tiny-qwen2-vl-2b.hier.p4-qint8"


@pytest.mark.parametrize("cell", [P1, HYMBA, HIER])
def test_a_sound_run_is_correct(tiny_root, cell):
    result = run_tiny(tiny_root, cell)
    assert result["correct"], result["checks"]
    assert result["metrics"]["train_tokens_per_s"]["value"] > 0


def unchanged_state(monkeypatch):
    """The optimizer's update hands the weights back as they were."""
    import repro.launch.train as train
    real = train.sgd

    def sgd(lr):
        opt = real(lr)
        return opt._replace(update=lambda g, p, s, step: (p, s))
    monkeypatch.setattr(train, "sgd", sgd)


def half_batch(monkeypatch):
    """The loss is the mean over the first half of the positions."""
    import repro.launch.train as train
    real = train.build

    def build(cfg):
        bundle = real(cfg)
        loss = bundle.loss_fn

        def half(params, batch):
            labels = batch["labels"]
            n = labels.shape[-1]
            mask = jnp.broadcast_to(jnp.arange(n) < n // 2, labels.shape)
            return loss(params, dict(batch, mask=mask))
        bundle.loss_fn = half
        return bundle
    monkeypatch.setattr(train, "build", build)


def no_exchange(monkeypatch):
    """Every reduction level leaves each learner's weights as they are."""
    import repro.core.hier_avg as hier_avg
    monkeypatch.setattr(hier_avg, "average_over",
                        lambda tree, *a, **k: tree)


@pytest.mark.parametrize("cell,fault", [
    (P1, unchanged_state), (P1, half_batch),
    (HYMBA, unchanged_state), (HYMBA, half_batch),
    (HIER, unchanged_state), (HIER, half_batch), (HIER, no_exchange)])
def test_a_broken_timed_path_is_not_correct(tiny_root, monkeypatch, cell,
                                            fault):
    fault(monkeypatch)
    result = run_tiny(tiny_root, cell)
    assert not result["correct"], result["checks"]
