"""What a run keeps on the device after set-up, and where its window ends,
on the CPU at the SMOKE width."""
import shutil
import tempfile
import time

import jax
import numpy as np
import pytest

from bench.loop import FIRST_STEPS, CellRun
from bench.tests import smoke


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    return smoke.build(tmp_path_factory.mktemp("smoke"))


def cell_run(spec, cell, seed=2**31 + 99, make_train_step=None):
    cfg = spec.config(spec.cell(cell)["config"])
    arch = spec.arch(cfg["model"]["arch"])
    traffic = spec.traffic(spec.cell(cell)["traffic"])
    workdir = tempfile.mkdtemp(prefix="bench-test-")
    run = CellRun(cfg, traffic, seed, jax.devices()[:1], workdir, arch,
                  make_train_step=make_train_step)
    return run, arch, workdir


def nbytes(arrays):
    """Bytes of the distinct buffers: on the CPU a host copy can be a view
    of its device buffer, which ``jax.live_arrays`` then lists twice."""
    return sum({x.unsafe_buffer_pointer(): x.nbytes for x in arrays}.values())


def test_set_up_keeps_the_checks_states_on_the_host(spec):
    """A step that donates its state (Adam's, ``toy_adam``): after set-up
    the device holds the trainer's live state and the kept batches, and
    what the check reads of the first steps is on the host."""
    before = jax.live_arrays()
    seen = {id(x) for x in before}
    run, _, workdir = cell_run(spec, smoke.TOY_ADAM_CELL)
    try:
        run.setup()
        new = [x for x in jax.live_arrays() if id(x) not in seen]
        state = jax.tree.leaves(run.seg.trainer.state)
        batches = jax.tree.leaves(run.first_batches)
        assert nbytes(new) <= 1.25 * nbytes(state) + nbytes(batches)
        p0, g1, pn = run.kept
        for tree in (p0, pn, *g1.trees):
            assert all(type(v) is np.ndarray for v in tree.values())
        assert all(v.dtype == np.float32 for v in p0.values())
        assert set(g1) == set(p0) == set(pn)
    finally:
        run.finish()
        shutil.rmtree(workdir, ignore_errors=True)


STEP_S = 0.2


def sleepy_step(arch):
    def make(model, devices):
        inner = arch.make_train_step(model, devices)

        def step(state, batch):
            time.sleep(STEP_S)
            return inner(state, batch)
        return step
    return make


def test_the_window_ends_within_one_step_of_its_close(spec):
    """With 0.2 s steps, a 1 s window's last step begins before the close,
    so ends within one step of it; every other step of the window ends
    inside it and is counted."""
    arch = spec.arch("toy_mlp")
    run, _, workdir = cell_run(spec, smoke.TOY_CELL,
                               make_train_step=sleepy_step(arch))
    try:
        run.setup()
        run.window(1.0)
    finally:
        run.finish()
        shutil.rmtree(workdir, ignore_errors=True)
    steps = run.steps[FIRST_STEPS:]
    last = steps[-1]
    assert last.t_ask < run.t_w1 < last.t_done
    assert last.t_done - run.t_w1 < last.t_done - last.t_ask
    assert run.in_window() == steps[:-1]
    assert [s.step for s in steps] == list(range(FIRST_STEPS + 1,
                                                 FIRST_STEPS + 1 + len(steps)))
    assert 2 <= len(run.in_window()) <= 1.0 / STEP_S
