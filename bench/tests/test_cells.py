"""Rehearsals of whole runs on the CPU at the SMOKE width: the cells with
tracing off and on, the refusal without a TPU, a new cell found by name, a
toy architecture run from its own files, what a traced run's record
carries, and ``correct`` coming out false when the timed path is broken."""
import io
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import flops, harness
from bench.spec import REPO, Spec
from bench.tests import smoke

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
    ("smoke.ckpt_preempt_x3", {"samples_per_s", "step_ms_p95",
                                "resume_s", "ckpt_commit_s", "setup_s"}),
    ("smoke.device_resize", {"samples_per_s", "step_ms_p95", "setup_s"}),
    (smoke.TOY_CELL, {"samples_per_s", "step_ms_p95", "resume_s",
                      "ckpt_commit_s", "setup_s"}),
    (smoke.TOY_ADAM_CELL, {"samples_per_s", "step_ms_p95", "resume_s",
                           "ckpt_commit_s", "setup_s"}),
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
    ("smoke.ckpt_preempt_x3", LAYER_METRICS | {"ckpt_blocked_ms",
                                                "ckpt_drain_ms",
                                                "restore_ms"}),
    ("smoke.device_resize", LAYER_METRICS),
    (smoke.TOY_CELL, LAYER_METRICS - smoke.TOY_LEAVES_OUT
     | {"ckpt_blocked_ms", "ckpt_drain_ms", "restore_ms"}),
    (smoke.TOY_ADAM_CELL, LAYER_METRICS - smoke.TOY_LEAVES_OUT
     | {"ckpt_blocked_ms", "ckpt_drain_ms", "restore_ms"}),
])
def test_traced_run(spec, monkeypatch, cell, metrics):
    """The trace-on path, with the device part of the reduction (which
    needs a TPU's trace) replaced by fixed numbers."""
    fake_trace(monkeypatch)
    result, _ = run(spec, cell, trace=1)
    assert result["correct"] is True
    assert set(result["metrics"]) == metrics
    assert result["metrics"]["device_idle_share"]["value"] == 75.0
    assert result["device"]["busy_s"] == 0.5
    assert result["breakdown"]["idle_gaps"] == [["x", 0.1]]


def fake_trace(monkeypatch):
    fake = {"devices": 1, "window_s": 2.0, "busy_s": 0.5, "collective_s": 0.0,
            "op_s": {"resize_convert_images.1": 0.2},
            "op_n": {"resize_convert_images.1": 10},
            "module_s": {"jit_train_step": 0.3},
            "module_n": {"jit_train_step": 10},
            "device_ops": [["fusion", 0.1]], "idle_gaps": [["x", 0.1]]}
    monkeypatch.setattr(harness.trace_reduce, "reduce_dir",
                        lambda *a, **k: fake)


@pytest.fixture(scope="module")
def traced_record(spec):
    """A traced ``smoke.ckpt_preempt_x3`` run's result and the record its
    metrics were read from."""
    records = []

    def record(*a, **k):
        records.append(real(*a, **k))
        return records[-1]

    with pytest.MonkeyPatch.context() as mp:
        fake_trace(mp)
        real = harness.record
        mp.setattr(harness, "record", record)
        result, _ = run(spec, "smoke.ckpt_preempt_x3", trace=1)
    return result, records[0]


def test_the_record_carries_the_programs_spans_and_counters(traced_record):
    """A reader added as a new file can read any span or counter of the
    window, on a clock that starts with it."""
    result, rec = traced_record
    assert result["correct"] is True
    spans, counters = rec["program_spans"], rec["program_counters"]
    assert {"decode", "step_dispatch", "step_sync"} <= {s["stage"]
                                                       for s in spans}
    assert set(spans[0]) == {"stage", "name", "thread", "t0", "dur",
                             "nbytes", "args"}
    assert all(0 <= s["t0"] <= rec["window_s"] for s in spans)
    assert counters and {c["name"] for c in counters} >= {"prefetch_buffer"}
    assert all(0 <= c["t"] <= rec["window_s"] for c in counters)


def test_the_window_stops_no_more_than_count_times(spec, traced_record):
    """A traffic's ``count`` of preemptions: after the last one the job
    trains on to the close."""
    _, rec = traced_record
    pre = spec.traffic("ckpt_preempt_x3")["preempt"]
    stops = [s["step"] for s in rec["steps"] if s["kind"] == "preempt"]
    assert stops == [pre["first_step"] + i * pre["every_steps"]
                     for i in range(pre["count"])]
    assert rec["steps"][-1]["step"] > stops[-1] + pre["every_steps"]


def test_step_mfu_reads_the_parents_formula(spec, traced_record):
    """``step_mfu`` through the architecture's ``train_flops_per_sample``
    is what it was through ``flops.alexnet_train_flops``."""
    result, rec = traced_record
    model = spec.config("smoke_caltech")["model"]
    rate = len(rec["steps"]) * rec["batch"] / rec["window_s"]
    parent = (100.0 * flops.alexnet_train_flops(model) * rate
              / (rec["chips"] * rec["peak_flops"]))
    assert result["metrics"]["step_mfu"]["value"] == parent


def test_cli_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(REPO / "bench" / "run.py"),
                        "--workload", "caltech101.ckpt_preempt_x3",
                        "--seed", "1",
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


def test_an_unknown_arch_fails_in_set_up(tmp_path):
    root = smoke.build(tmp_path).root
    path = root / "bench" / "configs" / "smoke_toy.json"
    cfg = json.loads(path.read_text())
    cfg["model"]["arch"] = "no_such_arch"
    path.write_text(json.dumps(cfg))
    with pytest.raises(FileNotFoundError,
                       match=r"bench/arch/no_such_arch\.py"):
        run(Spec(root), smoke.TOY_CELL)


# -- the timed path broken underneath: correct must come out false ----------
# each wraps the architecture's own step, so serves every architecture, also
# one that donates its state

def unchanged_step(arch):
    def make(model, devices):
        inner = arch.make_train_step(model, devices)

        def step(state, batch):
            params = jax.tree.map(jnp.copy, state["params"])
            new, metrics = inner(state, batch)
            return dict(new, params=params), metrics
        return step
    return make


def half_batch_step(arch):
    def make(model, devices):
        inner = arch.make_train_step(model, devices)

        def step(state, batch):
            h = jax.tree.leaves(batch)[0].shape[0] // 2
            return inner(state, jax.tree.map(lambda x: x[:h], batch))
        return step
    return make


@pytest.mark.parametrize("cell,make,fails", [
    ("smoke.ckpt_preempt_x3", unchanged_step, "grad_gap"),
    ("smoke.ckpt_preempt_x3", half_batch_step, "loss_gap"),
    (smoke.TOY_CELL, unchanged_step, "grad_gap"),
    # Adam's moments still move: the unchanged parameters show in the change
    (smoke.TOY_ADAM_CELL, unchanged_step, "delta_gap"),
    (smoke.TOY_ADAM_CELL, half_batch_step, "loss_gap"),
])
def test_a_broken_step_is_not_correct(spec, cell, make, fails):
    arch = spec.arch(spec.config(spec.cell(cell)["config"])["model"]["arch"])
    result, _ = run(spec, cell, make_train_step=make(arch))
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
    result, _ = run(spec, "smoke.ckpt_preempt_x3")
    assert result["correct"] is False
    assert result["checks"]["pixel_gap"]["value"] >= 0.2
