"""Readings of the control and the faults, for the limits of ``correct``.

For one seed, the cell's set-up at its own size (corpus, weights, pipeline,
trainer, the first steps through the timed path), then the numbers
``correct`` compares for

* ``program``: what the timed path produced, with the leaf that set each
  worst-leaf number (``grad_leaf``, ``delta_leaf``: the look behind them);
* ``control``: the reference one precision step below what the
  configuration states, put in the program's place: the step with float8
  e4m3 operands (:func:`bench.reference.step` with ``fp8``); for
  ``pixel_gap``, the device resize (float32 matmuls at ``HIGHEST``) as
  matmuls at ``Precision.HIGH`` (three passes);
* ``half_batch``: the reference stepping on the first half of each batch;
* ``unchanged``: a step that leaves the parameters as they were;
* ``altered_pixel``: the program's first batch with one pixel raised by one
  grey level where the pipeline produced it.
"""
import gc
import shutil
import tempfile

import numpy as np
from jax import lax

from . import check, harness, reference
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
    run = CellRun(cfg, traffic, seed, devices, workdir,
                  make_train_step=make_train_step)
    run.setup()
    run.finish()
    p = [check.to_host(x) for x in run.params_seen]
    program, (ri, rl, ref, batches, records) = harness.judge(
        run, keep_reference=True)
    program.pop("position_wrong", None)   # no window, no resume to check
    program.pop("restore_wrong", None)
    ref_losses, rp0, rg1, _, rpn = ref
    model, lr = cfg["model"], cfg["model"]["lr"]
    hw = model["in_hw"]

    keep = check.kept_leaves(rg1)
    grad = check.leaf_gaps({k: (p[0][k] - p[1][k]) / lr for k in keep},
                           rg1, keep)
    delta = check.leaf_gaps({k: p[2][k] - p[0][k] for k in keep},
                            {k: rpn[k] - rp0[k] for k in keep}, keep)
    program["grad_leaf"] = max(grad, key=grad.get)
    program["delta_leaf"] = max(delta, key=delta.get)
    program["leaves_left_out"] = sorted(set(rg1) - set(keep))

    def trajectory(**kw):
        tl, p0, _, p1, pn = check.reference_steps(
            seed, model, ri, rl, devices[0], **kw)
        return check.training_gaps(tl, p0, p1, pn, ref_losses, rp0, rg1,
                                   rpn, lr)

    out = {"seed": seed, "program": program}
    out["control"] = trajectory(fp8=True)
    pixels = [np.stack([run.corpus.pixels(i) for i in rows])
              for rows in records]
    lower = [reference.resize_as_matmuls(x, hw, hw, lax.Precision.HIGH)
             for x in pixels]
    out["control"]["pixel_gap"] = max(
        float(np.max(np.abs(x - r.astype(np.float64))))
        for x, r in zip(lower, ri))
    out["half_batch"] = trajectory(rows=cfg["batch"] // 2)
    out["unchanged"] = check.training_gaps(ref_losses, rp0, rp0, rp0,
                                           ref_losses, rp0, rg1, rpn, lr)
    altered = [tuple(np.array(x) for x in b) for b in batches[:1]]
    altered[0][0][0, 5, 5, 0] += 1.0 / 255.0
    out["altered_pixel"] = {"pixel_gap": check.reference_batches(
        run.corpus, altered, hw)[3]}
    del run
    gc.collect()
    return out
