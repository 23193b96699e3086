"""CPU rehearsal of chip_smoke.py: its phases at SMOKE width, with the
Pallas kernels interpreted (the CPU backend's default), and its refusal to
run without a TPU."""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.configs import ALEXNET_SMOKE

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"
# SMOKE-width corpus: 64 images of 32x32 upsampled to the net's 64x64
CORPUS = dict(n_images=64, images_per_shard=8, hw=32)
BATCH = 8


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _child_env(**extra):
    return dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=str(ROOT / "src"), **extra)


def test_phases_train_resume_and_first_batch(smoke, tmp_path):
    corpus = smoke.build_corpus(str(tmp_path), 0, **CORPUS)
    err = smoke.check_first_batch(corpus, ALEXNET_SMOKE, BATCH)
    assert 0.0 <= err <= smoke.RESIZE_ATOL

    ckpt = str(tmp_path / "ckpt")
    step = smoke.A.make_train_step(ALEXNET_SMOKE)
    first = smoke.train(corpus, ALEXNET_SMOKE, ckpt, step, seed=0,
                        batch_size=BATCH)
    assert [h["step"] for h in first.history] == [1, 2, 3, 4, 5, 6]
    assert first.data_iter.state()["offset"] == 6

    resumed = smoke.resume(corpus, ALEXNET_SMOKE, ckpt, step, first, seed=0,
                           batch_size=BATCH)
    assert resumed.recovered_step == 6
    assert [h["step"] for h in resumed.history] == [7, 8]
    assert resumed.data_iter.state()["offset"] == 8


def test_resume_rejects_a_different_state(smoke, tmp_path):
    """The bit-identity check bites: a resumed state that differs from the
    run it came from fails the phase."""
    corpus = smoke.build_corpus(str(tmp_path), 0, **CORPUS)
    ckpt = str(tmp_path / "ckpt")
    step = smoke.A.make_train_step(ALEXNET_SMOKE)
    first = smoke.train(corpus, ALEXNET_SMOKE, ckpt, step, seed=0,
                        batch_size=BATCH, n_steps=2)
    first.state = smoke.init_state(ALEXNET_SMOKE, 1) | {
        "step": first.state["step"]}
    with pytest.raises(AssertionError, match="bit-identical"):
        smoke.resume(corpus, ALEXNET_SMOKE, ckpt, step, first, seed=0,
                     batch_size=BATCH)


def test_four_chip_phase_on_four_cpu_devices(tmp_path):
    code = textwrap.dedent(f"""
        import importlib.util, json, jax
        spec = importlib.util.spec_from_file_location("chip_smoke",
                                                      {str(SCRIPT)!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        from repro.configs import ALEXNET_SMOKE
        devices = jax.devices()
        assert len(devices) == 4, devices
        corpus = smoke.build_corpus({str(tmp_path)!r}, 0, **{CORPUS!r})
        out = smoke.four_chips(corpus, ALEXNET_SMOKE,
                               {str(tmp_path / "ckpt")!r}, devices, seed=0,
                               batch_size={BATCH})
        print(json.dumps(out))
    """)
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=600,
        env=_child_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert len(out["losses"]) == 3
    assert out["losses"] == pytest.approx(out["reference_losses"], rel=1e-3)


def test_refuses_to_run_without_a_tpu(tmp_path):
    r = subprocess.run(
        [sys.executable, str(SCRIPT)], cwd=ROOT, capture_output=True,
        text=True, timeout=300, env=_child_env(TMPDIR=str(tmp_path)))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr
    assert list(tmp_path.iterdir()) == []  # no corpus was built
