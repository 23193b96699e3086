"""Rehearsals of whole runs on the CPU at the SMOKE width: the cell with
tracing off and on, the refusal without a TPU, a new cell found by name,
and ``correct`` coming out false when the timed path is broken."""
import io
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.spec import REPO, Spec
from bench.tests import smoke
from repro.models import alexnet as A

SEED = 2**31 + 12345          # beyond 32 signed bits, as the driver's are


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    return smoke.build(tmp_path_factory.mktemp("smoke"))


def run(spec, cell, trace=0, seconds=2, **kw):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                       str(seconds), "--trace", str(trace)], spec=spec,
                      require_tpu=False, peaks=smoke.CPU_PEAKS, out=out,
                      err=err, **kw)
    assert rc == 0, err.getvalue()
    lines = err.getvalue().strip().splitlines()
    return json.loads(out.getvalue().strip().splitlines()[-1]), lines


@pytest.mark.parametrize("cell,metrics", [
    ("smoke.ckpt_preempt", {"samples_per_s", "step_ms_p95", "resume_s",
                            "ckpt_commit_s", "setup_s"}),
    ("smoke.device_resize", {"samples_per_s", "step_ms_p95", "setup_s"}),
])
def test_untraced_run(spec, cell, metrics):
    result, err = run(spec, cell)
    assert result["correct"] is True
    assert set(result["metrics"]) == metrics
    assert list(result)[-1] == "checks"
    assert result["device"]["count"] == spec.cell(cell)["chips"]
    assert result["programs_loaded_in_window"] == 0
    # the numbers compared are the last lines of standard error
    assert all(line.startswith("check ") for line in
               err[-len(result["checks"]):])


LAYER_METRICS = {"data_wait_ms", "decode_busy_ms", "resize_roofline",
                 "step_mfu", "device_step_ms", "device_idle_share"}


@pytest.mark.parametrize("cell,metrics", [
    ("smoke.ckpt_preempt", LAYER_METRICS | {"ckpt_blocked_ms",
                                            "ckpt_drain_ms", "restore_ms"}),
    ("smoke.device_resize", LAYER_METRICS),
])
def test_traced_run(spec, monkeypatch, cell, metrics):
    """The trace-on path, with the device part of the reduction (which
    needs a TPU's trace) replaced by fixed numbers."""
    fake = {"devices": 1, "window_s": 2.0, "busy_s": 0.5, "collective_s": 0.0,
            "op_s": {"resize_convert_images.1": 0.2},
            "op_n": {"resize_convert_images.1": 10},
            "module_s": {"jit_train_step": 0.3},
            "module_n": {"jit_train_step": 10},
            "device_ops": [["fusion", 0.1]], "idle_gaps": [["x", 0.1]]}
    monkeypatch.setattr(harness.trace_reduce, "reduce_dir",
                        lambda *a, **k: fake)
    result, _ = run(spec, cell, trace=1)
    assert result["correct"] is True
    assert set(result["metrics"]) == metrics
    assert result["metrics"]["device_idle_share"]["value"] == 75.0
    assert result["device"]["busy_s"] == 0.5
    assert result["breakdown"]["idle_gaps"] == [["x", 0.1]]


def test_cli_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(REPO / "bench" / "run.py"),
                        "--workload", "caltech101.ckpt_preempt", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_new_files_are_found_by_name(spec, tmp_path):
    """A later cell is a config file, a traffic file and a metric reader
    dropped into their directories plus entries in BENCHMARK.json."""
    root = smoke.build(tmp_path).root
    home = root / "bench"
    cfg = json.loads((home / "configs" / "smoke_caltech.json").read_text())
    cfg["name"] = "smoke_new"
    (home / "configs" / "smoke_new.json").write_text(json.dumps(cfg))
    traffic = json.loads((home / "traffic" / "device_resize.json").read_text())
    traffic["prefetch"] = 2
    (home / "traffic" / "new_mix.json").write_text(json.dumps(traffic))
    (home / "metrics" / "steps_in_window.py").write_text(
        "def read(rec):\n    return len(rec['steps'])\n")
    data = json.loads((root / "BENCHMARK.json").read_text())
    data["configs"].append({"name": "smoke_new", "source": "tests",
                            "file": "bench/configs/smoke_new.json",
                            "reduced": [], "why": "tests"})
    data["workloads"].append({"name": "smoke.new", "config": "smoke_new",
                              "traffic": "new_mix", "chips": 1,
                              "why": "tests"})
    data["end_to_end"].append({"name": "steps_in_window", "unit": "steps",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["smoke.new"]})
    (root / "BENCHMARK.json").write_text(json.dumps(data))
    result, _ = run(Spec(root), "smoke.new")
    assert result["correct"] is True
    assert result["metrics"]["steps_in_window"]["value"] == result["attempted"]


# -- the timed path broken underneath: correct must come out false ----------

def unchanged_step(cfg):
    @jax.jit
    def step(state, batch):
        loss = A.loss_fn(state["params"], *batch, cfg)
        return {"params": state["params"], "step": state["step"] + 1}, \
            {"loss": loss}
    return step


def half_batch_step(cfg):
    inner = A.make_train_step(cfg)

    def step(state, batch):
        images, labels = batch
        h = images.shape[0] // 2
        return inner(state, (images[:h], labels[:h]))
    return step


@pytest.mark.parametrize("make,fails", [
    (unchanged_step, "grad_gap"),
    (half_batch_step, "loss_gap"),
])
def test_a_broken_step_is_not_correct(spec, make, fails):
    result, _ = run(spec, "smoke.ckpt_preempt", make_train_step=make)
    assert result["correct"] is False
    c = result["checks"][fails]
    assert c["value"] > c["limit"]


def test_an_altered_batch_row_is_not_correct(spec, monkeypatch):
    from repro.kernels import preprocess

    real = preprocess.resize_convert

    def altered(x, *a, **k):
        out = real(x, *a, **k)
        return out.at[0, 5, 5, 0].add(0.25) if hasattr(out, "at") else \
            _bump(out)

    def _bump(out):
        out = out.copy()
        out[0, 5, 5, 0] += 0.25
        return out

    monkeypatch.setattr(preprocess, "resize_convert", altered)
    result, _ = run(spec, "smoke.ckpt_preempt")
    assert result["correct"] is False
    assert result["checks"]["pixel_gap"]["value"] >= 0.2
