"""One run of one cell, from the command line to the result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (corpus, state, pipeline, trainer, the first steps that compile),
then ``--seconds`` of measured window, then the saves still draining, then
the check against the reference.  With ``--trace 0`` the result carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiler trace of the window and the program's own spans.  The last
line of standard output is the result; the last lines of standard error
are the numbers compared, each beside its limit.
"""
from __future__ import annotations

import argparse
import gc
import json
import resource
import shutil
import sys
import tempfile
import time
from typing import Dict, Optional

import jax
from jax import monitoring

from repro import trace as program_trace

from . import check, trace_reduce
from .loop import CellRun
from .peaks import peaks_for
from .spec import Spec

TRACE_DIR = ".bench_trace"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)
CACHE_EVENTS = ("/jax/compilation_cache/cache_hits",)


class CompileLog:
    """Times of every compilation and every program loaded from the
    persistent cache in this process."""

    def __init__(self):
        self.times = []
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if name in COMPILE_EVENTS:
            self.times.append(time.monotonic())

    def _event(self, name, **kw):
        if name in CACHE_EVENTS:
            self.times.append(time.monotonic())

    def between(self, a: float, b: float) -> int:
        return sum(1 for t in self.times if a <= t <= b)


def profile_options():
    """Device events only.  The host tracer would record, besides any
    annotation, the runtime's own events, among them millions a second from
    laying out each uint8 batch for the device: they slow a traced step
    many times over and fill the host's memory within a minute."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    return options


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def record(run: CellRun, *, setup_s: float, peaks, tracer=None,
           t_epoch: Optional[float] = None, device: Optional[Dict] = None):
    """What the metric readers read: the window's steps, saves, commits and
    resumes on the host clock, the program's spans and counters, the trace's
    device numbers, and the counts of work that the architecture's module
    computes from shapes."""
    steps = run.in_window()
    saved, committed = run.ckpt_log.saved, run.ckpt_log.committed
    periodic = run.periodic_saves()
    missing = [s for s in periodic if s not in committed]
    if missing:
        raise RuntimeError(f"saves {missing} never committed to the slow tier")
    rec = {
        "window_s": run.t_w1 - run.t_w0,
        "batch": run.batch,
        "chips": len(run.devices),
        "setup_s": setup_s,
        "steps": [{"step": s.step, "s": s.t_done - s.t_ask,
                   "data_wait_s": s.data_wait_s, "kind": s.kind,
                   "save_blocked_s": (saved[s.step][1]
                                      if s.step in saved
                                      and s.kind != "preempt" else 0.0)}
                  for s in steps],
        "periodic_saves": [{"step": s, "blocked_s": saved[s][1],
                            "commit_s": committed[s] - saved[s][0]}
                           for s in periodic],
        "resumes": [{"s": r.t_end - r.t_begin,
                     "restore_s": r.restored.restore_s}
                    for r in run.resumes if r.t_end <= run.t_w1],
        "peak_flops": peaks.bf16_flops,
        "peak_bytes_s": peaks.hbm_bytes_s,
        "device": device,
        "spans": None,
    }
    rec.update(run.arch.counts(run.cfg, run.traffic, run.batch))
    if tracer is not None:
        lo, hi = run.t_w0 - t_epoch, run.t_w1 - t_epoch
        spans = [s for s in tracer.spans() if lo <= s.t0 <= hi]
        rec["spans"] = {
            "decode_s": sum(s.dur for s in spans
                            if s.stage == program_trace.STAGE_DECODE),
            "drain_s": [s.dur for s in spans
                        if s.stage == program_trace.STAGE_DRAIN],
        }
        # every span and counter of the window, times from its start
        rec["program_spans"] = [
            {"stage": s.stage, "name": s.name, "thread": s.thread,
             "t0": s.t0 - lo, "dur": s.dur, "nbytes": s.nbytes,
             "args": s.args} for s in spans]
        rec["program_counters"] = [
            {"name": c.name, "t": c.t - lo, "value": c.value}
            for c in tracer.counters() if lo <= c.t <= hi]
    return rec


def judge(run: CellRun, keep_reference: bool = False):
    """The numbers ``correct`` compares.  What set-up kept of the first
    steps is on the host already; the kept batches and the saved states
    come to the host and are freed on the device before the reference runs.
    With ``keep_reference``, also returns the program's and the reference's
    trajectories, the reference's batches and the program's, for the
    readings of the control and the faults."""
    arch, model = run.arch, run.model
    host_batches = [jax.device_get(b) for b in run.first_batches]
    prog = (list(run.first_losses), *run.kept)
    resumes = [(check.state_mismatches(r.saved_state, r.restored.state),
                check.batches_differ(r.next_batch, r.first_batch))
               for r in run.resumes]
    run.first_batches = run.kept = run.resumes = None
    gc.collect()
    numbers, ref_batches = arch.check_batches(run.corpus, host_batches, model)
    ref = arch.reference_steps(run.seed, model, ref_batches, run.devices[0])
    numbers.update(check.training_gaps(prog, ref))
    if run.traffic.get("preempt"):
        numbers["restore_wrong"] = sum(n for n, _ in resumes)
        # a window that held no resume has checked none
        numbers["position_wrong"] = (sum(1 for _, d in resumes if d)
                                     if resumes else 1)
    if keep_reference:
        return numbers, (prog, ref, ref_batches, host_batches)
    return numbers


def disk_writes() -> Dict[str, int]:
    """This process's ``write_bytes`` and ``cancelled_write_bytes`` so far
    (Linux ``/proc/self/io``): what it sent towards the disk, and the part
    of that it deleted before it was written back."""
    try:
        with open("/proc/self/io") as f:
            lines = f.read().splitlines()
    except OSError:
        return {}
    pairs = (line.split(": ") for line in lines if ": " in line)
    return {k: int(v) for k, v in pairs
            if k in ("write_bytes", "cancelled_write_bytes")}


def device_info(devices) -> Dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def main(argv=None, *, t_start: Optional[float] = None, spec: Spec = None,
         require_tpu: bool = True, peaks=None, make_train_step=None,
         out=None, err=None) -> int:
    t_start = time.monotonic() if t_start is None else t_start
    out, err = out or sys.stdout, err or sys.stderr
    args = parse(argv)
    spec = spec or Spec()
    cell = spec.cell(args.workload)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r}); "
              f"nothing was run", file=err)
        return 2
    if len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, JAX "
              f"found {len(devices)}", file=err)
        return 2
    devices = devices[:cell["chips"]]
    peaks = peaks or peaks_for(devices[0].device_kind)
    cfg, traffic = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    arch = spec.arch(cfg["model"]["arch"])
    compiles = CompileLog()
    workdir = tempfile.mkdtemp(prefix="bench-")
    trace_dir = spec.root / TRACE_DIR / args.workload
    try:
        run = CellRun(cfg, traffic, args.seed, devices, workdir, arch,
                      make_train_step=make_train_step)
        run.setup()
        tracer = t_epoch = t_trace = None
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            t_epoch = time.monotonic()
            tracer = program_trace.start()
            t_trace = time.monotonic()
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=profile_options())
        setup_s = time.monotonic() - t_start
        try:
            run.window(args.seconds)
        finally:
            if args.trace:
                t_stop = time.monotonic()
                jax.profiler.stop_trace()
        run.finish()
        if args.trace:
            program_trace.stop()
        n_compiled = compiles.between(run.t_w0, run.t_w1)
        device = device_info(devices)
        reduced = None
        if args.trace:
            rel = lambda t: t - t_trace  # noqa: E731
            reduced = trace_reduce.reduce_dir(
                str(trace_dir), rel(run.t_w0), rel(run.t_w1),
                host=[(n, rel(a), rel(b)) for n, a, b in run.host_activity()],
                done_s=[rel(s.t_done) for s in run.steps
                        if t_trace <= s.t_ask and s.t_done <= t_stop])
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
        rec = record(run, setup_s=setup_s, peaks=peaks, tracer=tracer,
                     t_epoch=t_epoch, device=reduced)
        metrics = spec.read_metrics(args.workload, bool(args.trace), rec)
        attempted = len(rec["steps"])
        numbers = judge(run)
        correct, checks = check.verdict(numbers, cfg["limits"])
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    result = {"correct": correct, "attempted": attempted, "failed": 0,
              "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["programs_loaded_in_window"] = n_compiled
    result["checks"] = checks
    print(f"bench: set-up {setup_s!r} s, of which {run.setup_phases}; "
          f"{len(run.ckpt_log.saved)} saves, {len(rec['resumes'])} "
          f"resumes in the window; peak host memory "
          f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss >> 10} MiB; "
          f"disk writes {disk_writes()}", file=err)
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=err)
    print(json.dumps(result), file=out, flush=True)
    return 0
