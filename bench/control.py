"""Readings of the control and the faults, for the limits of ``correct``.

For one seed, the cell's set-up at its own size (corpus, state, pipeline,
trainer, the first steps through the timed path), then the numbers
``correct`` compares for

* ``program``: what the timed path produced, with the leaf that set each
  worst-leaf number (``grad_leaf``, ``delta_leaf``: the look behind them);
* ``control``: the reference one precision step below what the
  configuration states, put in the program's place (the architecture's
  ``reference_steps(lower=True)``; for AlexNet float8 e4m3 operands), and
  the input's control (its ``control_input``; for AlexNet the device
  resize's matmuls at ``Precision.HIGH``);
* ``half_batch``: the reference stepping on the first half of each batch;
* ``unchanged``: a step that leaves the parameters as they were;
* the input's faults, from ``control_input`` (for AlexNet
  ``altered_pixel``: one pixel of the first batch raised by one grey level
  where the pipeline produced it).
"""
import gc
import shutil
import tempfile

import numpy as np

from . import check, harness
from .loop import CellRun
from .spec import Spec


def readings(spec: Spec, cell_name: str, seed: int, devices,
             make_train_step=None) -> dict:
    workdir = tempfile.mkdtemp(prefix="bench-cal-")
    try:
        return _readings(spec, cell_name, seed, devices, workdir,
                         make_train_step)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _readings(spec, cell_name, seed, devices, workdir, make_train_step):
    cell = spec.cell(cell_name)
    cfg, traffic = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    model = cfg["model"]
    arch = spec.arch(model["arch"])
    run = CellRun(cfg, traffic, seed, devices, workdir, arch,
                  make_train_step=make_train_step)
    run.setup()
    run.finish()
    program, (prog, ref, ref_batches, batches) = harness.judge(
        run, keep_reference=True)
    program.pop("position_wrong", None)   # no window, no resume to check
    program.pop("restore_wrong", None)
    _, p0, g1, pn = prog
    ref_losses, rp0, rg1, rpn = ref

    keep = check.kept_leaves(rg1)
    grad = check.leaf_gaps(g1, rg1, keep)
    delta = check.leaf_gaps(check.change(p0, pn), check.change(rp0, rpn),
                            keep)
    program["grad_leaf"] = max(grad, key=grad.get)
    program["delta_leaf"] = max(delta, key=delta.get)
    program["leaves_left_out"] = sorted(set(rg1) - set(keep))

    def trajectory(**kw):
        return check.training_gaps(
            arch.reference_steps(seed, model, ref_batches, devices[0], **kw),
            ref)

    out = {"seed": seed, "program": program}
    out["control"] = trajectory(lower=True)
    out["half_batch"] = trajectory(rows=cfg["batch"] // 2)
    out["unchanged"] = check.training_gaps(
        (ref_losses, rp0, {k: np.zeros_like(v) for k, v in rp0.items()}, rp0),
        ref)
    for name, numbers in arch.control_input(run.corpus, ref_batches, batches,
                                            model).items():
        out.setdefault(name, {}).update(numbers)
    del run
    gc.collect()
    return out
