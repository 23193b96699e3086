"""The paper's AlexNet mini-application (§III-B), end to end.

    PYTHONPATH=src python examples/alexnet_miniapp.py [--tier hdd|ssd|optane]

Generates a Caltech-101-like corpus on a simulated tier, trains AlexNet with
the full input pipeline, and prints per-step data-wait vs compute (the
paper's prefetch-overlap observable) plus a dstat-style I/O trace.

``--trace OUT.json`` adds per-op span collection (Chrome trace + Darshan
report); ``--metrics OUT.jsonl`` adds live telemetry (sampled gauge/counter
time series, Prometheus snapshot, per-step stall detection).  The two
compose: with both, the trace report embeds the metrics timeline.

``--ckpt DIR`` turns on fault-tolerant checkpointing: a
:class:`~repro.core.recovery.CheckpointManager` saves params *and* the
input-pipeline position into DIR every ``--ckpt-every`` steps.  Kill the
run, rerun with ``--resume``, and it restores the newest **valid**
checkpoint (walking back past torn/corrupt ones) and repositions the
iterator so no sample is skipped or replayed — the corpus is seeded, so a
rerun regenerates identical data::

    PYTHONPATH=src python examples/alexnet_miniapp.py \\
        --ckpt /tmp/alexckpt --steps 8
    PYTHONPATH=src python examples/alexnet_miniapp.py \\
        --ckpt /tmp/alexckpt --resume --steps 8

``--ckpt-engine direct|async|bb|asyncbb`` picks the checkpoint engine the
manager drives (the fused lifecycle: async engines overlap the save with
training; bb/asyncbb stage through a fast buffer under DIR first).
``--preempt-at N`` demos graceful preemption: at step N the trainer stops,
promotes the final save within ``--preempt-deadline`` seconds, and prints
the preemption report; rerun with ``--resume`` to restart exactly there::

    PYTHONPATH=src python examples/alexnet_miniapp.py \\
        --ckpt /tmp/alexckpt --ckpt-engine asyncbb --ckpt-every 2 \\
        --steps 8 --preempt-at 5
    PYTHONPATH=src python examples/alexnet_miniapp.py \\
        --ckpt /tmp/alexckpt --ckpt-engine asyncbb --resume --steps 8
"""
import argparse, os, sys, tempfile
sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro import metrics, trace
from repro.configs import ALEXNET_SMOKE as CFG
from repro.core import CheckpointManager, IOTracer, ResumableIterator, \
    image_pipeline, make_storage, sharded_image_pipeline
from repro.core import records
from repro.launch.compile_cache import use_compile_cache
from repro.models import alexnet as A
from repro.train.trainer import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tier", default="ssd")
    ap.add_argument("--threads", type=int, default=4)
    ap.add_argument("--prefetch", type=int, default=1)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--sharded", action="store_true",
                    help="stream the corpus from multi-record shards via "
                         "the interleaved read engine instead of "
                         "one-file-per-image")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="collect per-op spans and write a Chrome trace "
                         "(open in Perfetto); also prints the per-stage "
                         "Darshan-style report")
    ap.add_argument("--metrics", metavar="OUT.jsonl", default=None,
                    help="enable live telemetry: sample the metrics "
                         "registry (prefetch occupancy, storage latency "
                         "sketches, per-step heartbeat) into a JSONL time "
                         "series and print the final Prometheus-text "
                         "snapshot; composes with --trace")
    ap.add_argument("--ckpt", metavar="DIR", default=None,
                    help="checkpoint params + pipeline position into DIR "
                         "via CheckpointManager (keep-last retention, "
                         "corruption-aware restore)")
    ap.add_argument("--ckpt-every", type=int, default=5,
                    help="save every N steps (with --ckpt; default 5)")
    ap.add_argument("--ckpt-engine", default="direct",
                    choices=("direct", "async", "bb", "asyncbb"),
                    help="checkpoint engine the manager drives (with "
                         "--ckpt; bb/asyncbb stage through a fast buffer "
                         "under DIR)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest valid checkpoint from --ckpt "
                         "and continue — params and input position")
    ap.add_argument("--preempt-at", type=int, default=None, metavar="STEP",
                    help="demo graceful preemption: stop at STEP, promote "
                         "the final save within the deadline, print the "
                         "preemption report (requires --ckpt)")
    ap.add_argument("--preempt-deadline", type=float, default=5.0,
                    help="graceful-shutdown budget in seconds (with "
                         "--preempt-at; default 5)")
    args = ap.parse_args()
    use_compile_cache()
    if args.resume and not args.ckpt:
        ap.error("--resume requires --ckpt DIR")
    if args.preempt_at is not None and not args.ckpt:
        ap.error("--preempt-at requires --ckpt DIR")

    tracer = IOTracer(0.25)
    st = make_storage(args.tier, tempfile.mkdtemp(), tracer, time_scale=0.2)
    if args.sharded:
        shard_paths, shard_labels = records.write_sharded_image_dataset(
            st, 128, 16, mean_hw=(64, 64), n_classes=CFG.n_classes)
    else:
        paths, labels = records.write_image_dataset(
            st, 128, mean_hw=(64, 64), n_classes=CFG.n_classes)
    tracer.reset()

    def build_pipeline(seed=0, repeat=True):
        if args.sharded:
            return sharded_image_pipeline(st, shard_paths, shard_labels,
                                          batch_size=16,
                                          cycle_length=args.threads,
                                          num_parallel_calls=args.threads,
                                          prefetch=args.prefetch,
                                          out_hw=(CFG.in_hw, CFG.in_hw),
                                          seed=seed, repeat=repeat)
        return image_pipeline(st, paths, labels, batch_size=16,
                              num_parallel_calls=args.threads,
                              prefetch=args.prefetch,
                              out_hw=(CFG.in_hw, CFG.in_hw),
                              seed=seed, repeat=repeat)

    ckpt_mgr = None
    if args.ckpt:
        # resumable position needs finite epochs: one Dataset per epoch,
        # shuffled by a per-epoch seed the factory can replay on restore
        ds = ResumableIterator(lambda ep: build_pipeline(seed=ep,
                                                         repeat=False))
        # bb/asyncbb stage through a fast buffer inside the checkpoint dir
        # (persists across restarts: a staged-not-drained step is still
        # restorable after a preemption)
        fast = (make_storage("native", os.path.join(args.ckpt, "fastbuf"))
                if args.ckpt_engine in ("bb", "asyncbb") else None)
        ckpt_mgr = CheckpointManager(make_storage("native", args.ckpt),
                                     "ckpt/alexnet", keep_last=3,
                                     engine=args.ckpt_engine,
                                     fast_storage=fast)
    else:
        ds = build_pipeline(repeat=True)

    params = A.init_params(jax.random.PRNGKey(0), CFG)
    state = {"params": params, "step": jnp.int32(0)}
    train_step = A.make_train_step(CFG)

    collector = trace.start() if args.trace else None
    sampler = None
    stall = None
    if args.metrics:
        metrics.start()
        sampler = metrics.Sampler(interval_s=0.1, jsonl_path=args.metrics)
        sampler.start()
        stall = metrics.StallDetector(min_samples=4)
    tr = Trainer(train_step, state, iter(ds), stall_detector=stall,
                 checkpointer=ckpt_mgr, ckpt_every=args.ckpt_every,
                 resume=args.resume,
                 preempt_deadline_s=args.preempt_deadline)
    if args.preempt_at is not None:
        def _maybe_preempt(step, _m, _tr=tr, _at=args.preempt_at):
            if step >= _at:
                _tr.preempt()
        tr.on_step = _maybe_preempt
    if args.resume:
        if tr.recovered_step is not None:
            pos = ds.state()
            print(f"resumed from step {tr.recovered_step} "
                  f"(latest valid checkpoint in {args.ckpt}) — input "
                  f"pipeline at epoch {pos['epoch']}, "
                  f"batch offset {pos['offset']}")
        else:
            print(f"--resume: no valid checkpoint under {args.ckpt}; "
                  f"starting fresh")
    tr.run(args.steps)
    if ckpt_mgr is not None:
        tr.wait_for_checkpoints()  # drain async saves before reporting
        ckpt_mgr.close()
    tr.close()  # repeat() pipeline: stop the prefetch producer promptly
    rep = tr.report()
    if rep["preemption"] is not None:
        p = rep["preemption"]
        print(f"preempted: committed step {p['committed_step']} in "
              f"{p['preempt_s']:.3f}s (deadline {p['deadline_s']}s, "
              f"met={p['deadline_met']}, abandoned={p['abandoned_steps']}) "
              f"— rerun with --resume to restart there")
    print(f"tier={args.tier} threads={args.threads} prefetch={args.prefetch}"
          f" sharded={args.sharded}")
    print(f"  data-wait fraction: {rep['data_wait_frac']:.1%} "
          f"(prefetch hides I/O when ~0)")
    print(f"  losses: {[round(h['loss'], 3) for h in tr.history]}")
    print("dstat-style read trace (MB/s):")
    print(tracer.to_csv())
    metric_points = None
    if sampler is not None:
        sampler.stop()
        metric_points = sampler.points()
        print(f"\nmetrics time series written to {args.metrics} "
              f"({len(metric_points)} samples)")
        print(metrics.to_prometheus_text(metrics.get_registry()))
        if stall is not None and stall.events:
            print(f"stalls detected: {stall.summary()}")
        metrics.stop()
    if collector is not None:
        trace.stop()
        trace.dump_chrome_trace(collector, args.trace,
                                process_name="alexnet-miniapp")
        print(f"\nChrome trace written to {args.trace}")
        print(trace.to_markdown(collector.spans(),
                                title="Per-stage I/O report",
                                counters=collector.counters(),
                                metrics_series=metric_points))


if __name__ == "__main__":
    main()
