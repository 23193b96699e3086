"""Fig. 15 (ours): goodput and time-to-recover under periodic preemption.

The preemption-safe checkpoint lifecycle, measured end-to-end.  Per slow
tier (hdd/ssd/optane/lustre), a training loop runs under the fused
:class:`~repro.core.recovery.CheckpointManager` (``engine="asyncbb"``:
snapshot-only blocking, optane stage, background drain) and is preempted
every ``steps_per_cycle`` steps with a graceful-shutdown budget
(:meth:`Trainer.preempt`): the newest in-flight save is promoted to its
fast-tier commit inside the deadline, older queued snapshots abandoned.
Each cycle then restarts — a fresh manager resumes from the best of both
tiers and repositions the seekable input iterator (O(1), no replay).

Emitted per tier:

* ``goodput_frac`` — useful compute time over compute + preemption
  overhead (final-save promotion + restart), the headline cost of a
  preemption cycle; a ratio, robust to box speed.
* ``recover_s`` / ``recovery_per_s`` — mean wall time from "new node"
  to training-ready (manager + restore + iterator seek), and its
  higher-is-better reciprocal for the CI regression gate.
* ``preempt_s`` — mean stop-path wall (final snapshot + promotion).
* ``deadline_met`` / ``resumed_at_preempted_step`` — the lifecycle
  contract: with a sane deadline every cycle commits the preempted step
  and every restart resumes exactly there.

Two hdd-only sections ride along:

* **hung-drain injection**: a drain stream wedges mid-save
  (:meth:`FaultyStorage.hang`); the watchdog must detect the stall
  within ``2x drain_stall_timeout``, abort the stream, re-queue its
  chunks, and the save must still commit (``drain_stalls``/
  ``drain_aborts`` reported).
* **fused-vs-bare overhead**: training-thread blocked time through the
  fused manager vs the bare ``asyncbb`` engine —
  the lifecycle layer must cost <= 1.1x blocked (1.3x in --smoke,
  where ms-scale snapshots make the ratio noisy).

Machine-readable ``BENCH_preemption.json``; gated leaves:
``goodput_frac`` and ``recovery_per_s``.

    PYTHONPATH=src python -m benchmarks.fig15_preemption [--smoke]
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, "src")

import numpy as np

from repro.core import make_storage
from repro.core.engine import CheckpointEngine
from repro.core.dataset import Dataset, ResumableIterator
from repro.core.faults import FaultyStorage
from repro.core.recovery import CheckpointManager
from repro.train.trainer import Trainer

from .common import RESULTS_DIR, SCRATCH, emit

CKPT_TIME_SCALE = float(os.environ.get("REPRO_CKPT_TIME_SCALE", "1.0"))
TIERS = ("hdd", "ssd", "optane", "lustre")
PREFIX = "ck/m"
DEADLINE_S = 30.0
WATCHDOG_TIMEOUT_S = 0.2


def make_state(mb: float):
    rng = np.random.default_rng(0)
    n = int(mb * 1024 * 256)
    return {"w": rng.normal(size=(n,)).astype(np.float32),
            "step": np.int64(0)}


def make_data_iter():
    """Seekable input: restart repositions arithmetically, no replay."""
    return ResumableIterator(
        lambda ep, start=0: Dataset(
            lambda: (np.float32(i) for i in range(start, 1 << 30))))


def make_train_step(compute_s: float):
    def train_step(state, batch):
        time.sleep(compute_s)
        out = dict(state)
        out["step"] = np.int64(int(state["step"]) + 1)
        return out, {"loss": np.float32(batch)}
    return train_step


def preemption_cycles(make_mgr, state_mb, compute_s, n_cycles,
                      steps_per_cycle, ckpt_every, deadline_s=DEADLINE_S):
    """Run preempt/restart cycles; every restart must land exactly on the
    step the previous cycle's promotion committed."""
    steps_done = 0
    preempt_times, recover_times = [], []
    deadline_met = True
    resumed_ok = True
    last_committed = None
    for _ in range(n_cycles):
        t0 = time.monotonic()
        mgr = make_mgr()
        tr = Trainer(make_train_step(compute_s), make_state(state_mb),
                     make_data_iter(), checkpointer=mgr,
                     ckpt_every=ckpt_every, preempt_deadline_s=deadline_s)
        recover_times.append(time.monotonic() - t0)
        if last_committed is not None:
            resumed_ok &= tr.recovered_step == last_committed
        stop_at = (tr.recovered_step or 0) + steps_per_cycle

        def on_step(step, _m, _tr=tr, _stop=stop_at):
            if step >= _stop:
                _tr.preempt()
        tr.on_step = on_step
        tr.run(steps_per_cycle + 1)  # the +1 turn executes the stop path
        steps_done += len(tr.history)
        rep = tr.report()["preemption"]
        assert rep is not None
        deadline_met &= bool(rep["deadline_met"])
        last_committed = rep["committed_step"]
        preempt_times.append(rep["preempt_s"])
        # the node is gone: drains finish during scheduler downtime, off
        # the preemption critical path — not charged to goodput
        mgr.close()
        tr.close()
    compute_total = steps_done * compute_s
    overhead = sum(preempt_times) + sum(recover_times)
    return {
        "steps_done": steps_done,
        "goodput_frac": round(compute_total / (compute_total + overhead), 4),
        "preempt_s": round(float(np.mean(preempt_times)), 4),
        "recover_s": round(float(np.mean(recover_times)), 4),
        "recovery_per_s": round(1.0 / max(float(np.mean(recover_times)),
                                          1e-9), 3),
        "deadline_met": deadline_met,
        "resumed_at_preempted_step": resumed_ok,
    }


def hung_drain_section(root, state_mb):
    """Wedge one drain stream mid-save; the watchdog must absorb it."""
    slow = FaultyStorage(make_storage("hdd", os.path.join(root, "wd_slow"),
                                     time_scale=CKPT_TIME_SCALE))
    fast = make_storage("optane", os.path.join(root, "wd_fast"),
                        time_scale=CKPT_TIME_SCALE)
    mgr = CheckpointManager(slow, PREFIX, engine="asyncbb",
                            fast_storage=fast, keep_last=2,
                            drain_streams=2, drain_chunk=1 << 18,
                            drain_stall_timeout=WATCHDOG_TIMEOUT_S)
    slow.hang(on=".data-")  # one-shot: the re-queued chunk succeeds
    state = make_state(state_mb)
    t0 = time.monotonic()
    mgr.save(1, state)
    mgr.wait()
    wall = time.monotonic() - t0
    stalls, aborts = mgr.engine.drain_stalls, mgr.engine.drain_aborts
    committed = mgr.latest_valid() == 1 and 1 in mgr.all_steps()
    slow.heal()  # un-park the abandoned stream thread
    mgr.close()
    return {
        "drain_stalls": stalls,
        "drain_aborts": aborts,
        "save_committed": committed,
        "wall_s": round(wall, 4),
        "watchdog_timeout_s": WATCHDOG_TIMEOUT_S,
        # detection bound: stall absorbed within 2x timeout + the drain
        "detected_in_budget": stalls >= 1 and committed,
    }


def fused_overhead_section(root, state_mb, n_saves, reps=3):
    """Training-thread blocked time: fused manager vs bare asyncbb."""
    def blocked_with(make_ck, tag):
        best = None
        for r in range(reps):
            ck = make_ck(f"{tag}{r}")
            state = make_state(state_mb)
            for i in range(1, n_saves + 1):
                ck.save(i, state)
            ck.wait()
            total = sum(ck.blocked_s)
            ck.close()
            best = total if best is None else min(best, total)
        return best

    def tiers(tag):
        return (make_storage("optane", os.path.join(root, f"{tag}_fast"),
                             time_scale=CKPT_TIME_SCALE),
                make_storage("hdd", os.path.join(root, f"{tag}_slow"),
                             time_scale=CKPT_TIME_SCALE))

    def bare(tag):
        fast, slow = tiers(tag)
        return CheckpointEngine((fast, slow), PREFIX, snapshot="async",
                                drain_streams=4, drain_chunk=1 << 20)

    def fused(tag):
        fast, slow = tiers(tag)
        return CheckpointManager(slow, PREFIX, engine="asyncbb",
                                 fast_storage=fast, keep_last=3,
                                 drain_streams=4, drain_chunk=1 << 20)

    bare_s = blocked_with(bare, "bare")
    fused_s = blocked_with(fused, "fused")
    return {
        "bare_blocked_s": round(bare_s, 4),
        "fused_blocked_s": round(fused_s, 4),
        "blocked_ratio": round(fused_s / max(bare_s, 1e-9), 4),
    }


def run(state_mb=4.0, compute_s=0.02, n_cycles=3, steps_per_cycle=6,
        ckpt_every=2, n_overhead_saves=6, smoke=False,
        name="fig15_preemption", json_path=None) -> dict:
    rows = []
    tiers_out = {}
    with tempfile.TemporaryDirectory(dir=SCRATCH) as root:
        for tier in TIERS:
            slow = make_storage(tier, os.path.join(root, f"{tier}_slow"),
                                time_scale=CKPT_TIME_SCALE)
            fast = make_storage("optane", os.path.join(root, f"{tier}_fast"),
                                time_scale=CKPT_TIME_SCALE)

            def make_mgr(_slow=slow, _fast=fast):
                return CheckpointManager(_slow, PREFIX, engine="asyncbb",
                                         fast_storage=_fast, keep_last=3,
                                         drain_streams=4,
                                         drain_chunk=1 << 20)
            res = preemption_cycles(make_mgr, state_mb, compute_s,
                                    n_cycles, steps_per_cycle, ckpt_every)
            tiers_out[tier] = res
            rows.append(
                f"tier={tier},goodput_frac={res['goodput_frac']:.3f},"
                f"preempt_s={res['preempt_s']:.3f},"
                f"recover_s={res['recover_s']:.3f},"
                f"deadline_met={res['deadline_met']},"
                f"resumed_at_preempted_step="
                f"{res['resumed_at_preempted_step']}")

        watchdog = hung_drain_section(root, state_mb)
        rows.append(
            f"section=hung_drain,drain_stalls={watchdog['drain_stalls']},"
            f"drain_aborts={watchdog['drain_aborts']},"
            f"save_committed={watchdog['save_committed']},"
            f"wall_s={watchdog['wall_s']:.3f}")

        overhead = fused_overhead_section(root, state_mb, n_overhead_saves)
        rows.append(
            f"section=fused_overhead,bare_blocked_s="
            f"{overhead['bare_blocked_s']:.4f},"
            f"fused_blocked_s={overhead['fused_blocked_s']:.4f},"
            f"blocked_ratio={overhead['blocked_ratio']:.3f}")

    ratio_limit = 1.3 if smoke else 1.1
    ok_contract = all(t["deadline_met"] and t["resumed_at_preempted_step"]
                      for t in tiers_out.values())
    ok_watchdog = watchdog["detected_in_budget"]
    ok_overhead = overhead["blocked_ratio"] <= ratio_limit
    derived = (
        f"preemption contract (deadline met + resume at preempted step) on "
        f"all tiers = {ok_contract}; hung drain absorbed = {ok_watchdog} "
        f"(stalls={watchdog['drain_stalls']}); fused/bare blocked ratio = "
        f"{overhead['blocked_ratio']:.3f} (acceptance: <={ratio_limit}); "
        f"goodput_frac: " + ", ".join(
            f"{t}={tiers_out[t]['goodput_frac']:.3f}" for t in TIERS))
    emit(name, rows, derived)

    payload = {
        "benchmark": name,
        "config": {
            "state_mb": state_mb, "compute_s": compute_s,
            "n_cycles": n_cycles, "steps_per_cycle": steps_per_cycle,
            "ckpt_every": ckpt_every, "deadline_s": DEADLINE_S,
            "n_overhead_saves": n_overhead_saves,
            "time_scale": CKPT_TIME_SCALE, "tiers": list(TIERS),
            "engine": "asyncbb",
        },
        "tiers": tiers_out,
        "hung_drain": watchdog,
        "fused_overhead": overhead,
        "acceptance": {
            "preemption_contract": ok_contract,
            "hung_drain_absorbed": ok_watchdog,
            "fused_blocked_ratio_ok": ok_overhead,
            "fused_blocked_ratio_limit": ratio_limit,
        },
    }
    os.makedirs(RESULTS_DIR, exist_ok=True)
    out_json = json_path or os.path.join(RESULTS_DIR, "BENCH_preemption.json")
    with open(out_json, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {out_json}")
    return payload


def run_smoke() -> dict:
    """Tiny-scale CI variant: same output shape, seconds of runtime."""
    return run(state_mb=1.0, compute_s=0.01, n_cycles=2, steps_per_cycle=4,
               n_overhead_saves=4, smoke=True)


if __name__ == "__main__":
    smoke = "--smoke" in sys.argv
    payload = run_smoke() if smoke else run()
    acc = payload["acceptance"]
    ok = all(acc[k] for k in ("preemption_contract", "hung_drain_absorbed",
                              "fused_blocked_ratio_ok"))
    print(f"# preemption_contract={acc['preemption_contract']} "
          f"hung_drain_absorbed={acc['hung_drain_absorbed']} "
          f"fused_blocked_ratio_ok={acc['fused_blocked_ratio_ok']}")
    if not ok:
        sys.exit(1)
