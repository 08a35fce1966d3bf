"""chip_smoke.py on the CPU: its phases end to end at a tiny size with
the Pallas kernels interpreted, its learner mesh on four host devices,
and its refusals (no TPU; no repository beside it).

On the CPU the platform check is steered here, through ``Settings``: the
script itself has no fallback and no option that would let it pass
without a TPU.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"

# reduced widths, two layers, a 4096-element bucket row; the kernels run
# in interpret mode, so no compiled program holds a tpu_custom_call
TINY = dict(
    platform="cpu", kernel_impl="pallas_interpret", expect_kernels=False,
    train_argv=("--arch", "hymba-1.5b", "--reduced", "--layers", "2",
                "--learners", "4", "--s", "2",
                "--plan", "local@2:qint8:128/global@4", "--no-overlap",
                "--rounds", "2", "--batch", "2", "--seq", "16",
                "--lr", "0.01"),
    serve_argv=("--arch", "qwen2-vl-2b", "--reduced", "--paged",
                "--requests", "3", "--slots", "2", "--prompt-len", "8",
                "--max-new", "4", "--block-size", "16",
                "--decode-impl", "pallas_interpret"),
    bucket_row=4096)


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod          # dataclasses look it up
    spec.loader.exec_module(mod)
    return mod


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.update(extra)
    return env


def test_chip_smoke_phases_run_on_cpu_in_interpret_mode(monkeypatch,
                                                        tmp_path, capsys):
    # the entry points place JAX's compile cache here, not in the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    cs = _load_smoke()
    s = cs.Settings(**TINY)
    cs.one_chip(s, cs.require_platform("cpu"))
    out = capsys.readouterr().out
    losses = json.loads(out.split("losses: ")[1].splitlines()[0])
    assert len(losses) == 2 and all(math.isfinite(v) for v in losses)
    assert "wire bit-identical True; unpack bit-identical True" in out
    assert out.count("batched_qr [4, ") >= 2
    assert "flash_decode logits vs xla oracle" in out
    for phase in ("train", "serve", "codec"):
        assert f"\n{phase}: wall " in out


def test_chip_smoke_learner_mesh_on_four_host_devices(tmp_path):
    """``--chips 4``'s comparison on four CPU devices: one learner per
    device against the stacked run, same losses."""
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(ROOT)!r})
        import chip_smoke as cs
        s = cs.Settings(**{TINY!r})
        cs.mesh_vs_stacked(s, cs.require_platform("cpu"))
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=ROOT,
        env=_env(PYTHONPATH=str(ROOT / "src"),
                 XLA_FLAGS="--xla_force_host_platform_device_count=4",
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "mesh={'pod': 1, 'group': 2, 'local': 2" in out.stdout
    assert "each device holds exactly one learner's shard" in out.stdout
    assert "loss agreement mesh vs stacked" in out.stdout


def test_chip_smoke_fails_without_a_tpu(tmp_path):
    out = subprocess.run(
        [sys.executable, str(SCRIPT)], capture_output=True, text=True,
        timeout=300, cwd=ROOT,
        env=_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "need a tpu device" in out.stderr


def test_chip_smoke_fails_outside_the_repository(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    out = subprocess.run([sys.executable, str(alone)], capture_output=True,
                         text=True, timeout=120, cwd=tmp_path, env=_env())
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_compile_cache_dir_from_env_or_checkout(tmp_path):
    """The entry points' compile cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set, which JAX itself reads and the code leaves alone, else the
    fixed ``.jax_cache`` at the root of the checkout."""
    code = ("import jax; from repro.runtime import use_compile_cache; "
            "print(use_compile_cache()); "
            "print(jax.config.jax_compilation_cache_dir)")
    with_env = _env(JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    without = _env()
    without.pop("JAX_COMPILATION_CACHE_DIR", None)
    for env, want in ((with_env, tmp_path), (without, ROOT / ".jax_cache")):
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120, cwd=tmp_path,
            env=dict(env, PYTHONPATH=str(ROOT / "src")))
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.split() == [str(want)] * 2
