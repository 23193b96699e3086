"""Burst-buffer & async checkpointing demo (paper §V-C, the 2.6x result).

    PYTHONPATH=src python examples/burst_buffer_checkpoint.py          # paper's comparison
    PYTHONPATH=src python examples/burst_buffer_checkpoint.py --async  # + async engine

Checkpoints a ~75MB state to (a) direct HDD, (b) direct Optane, (c) Optane
burst buffer with multi-stream async HDD drain, printing blocked time per
strategy and proving the slow tier ends up with every checkpoint.  With
``--async``, also runs the two async presets of the one
:class:`CheckpointEngine`: ``async`` (training blocks only for the host
snapshot — milliseconds — while the sharded write to HDD runs on a
background writer thread) and ``asyncbb`` (snapshot blocks; the Optane
stage *and* the intra-file parallel HDD drain both run in background
threads, so not even the fast-tier write is paid by the training thread).
Each is built through :class:`CheckpointManager`.
"""
import os, sys, tempfile, time
sys.path.insert(0, "src")

import numpy as np

from repro.core import CheckpointManager, make_storage
from repro.core.checkpoint import CheckpointSaver


def main():
    run_async = "--async" in sys.argv[1:]
    rng = np.random.default_rng(0)
    state = {"params": {f"layer{i}": rng.normal(size=(512, 9216)).astype(np.float32)
                        for i in range(4)}}
    nbytes = sum(v.nbytes for v in state["params"].values())
    print(f"checkpoint payload: {nbytes/1e6:.0f} MB")
    root = tempfile.mkdtemp()
    ts = 1.0

    hdd = make_storage("hdd", os.path.join(root, "hdd"), time_scale=ts)
    d = CheckpointManager(hdd, "direct_hdd/m")
    d.save(1, state)
    print(f"direct-to-HDD blocked:    {d.blocked_s[0]:.2f}s")

    opt = make_storage("optane", os.path.join(root, "opt"), time_scale=ts)
    d2 = CheckpointManager(opt, "direct_opt/m")
    d2.save(1, state)
    print(f"direct-to-Optane blocked: {d2.blocked_s[0]:.2f}s")

    fast = make_storage("optane", os.path.join(root, "bb_fast"), time_scale=ts)
    slow = make_storage("hdd", os.path.join(root, "bb_slow"), time_scale=ts)
    bb = CheckpointManager(slow, "bb/m", engine="bb", fast_storage=fast)
    t0 = time.monotonic()
    bb.save(1, state)
    print(f"burst-buffer blocked:     {bb.blocked_s[0]:.2f}s "
          f"(training continues while the drain runs)")
    bb.wait()
    print(f"async drain finished at t={time.monotonic()-t0:.2f}s")
    restored = CheckpointSaver(slow, "bb/m").restore_pytree(state)
    ok = all(np.array_equal(restored["params"][k], state["params"][k])
             for k in state["params"])
    print(f"slow-tier copy bit-identical: {ok}")
    bb.close()

    if run_async:
        ahdd = make_storage("hdd", os.path.join(root, "async_hdd"),
                            time_scale=ts)
        ac = CheckpointManager(ahdd, "async/m", engine="async", n_shards=4)
        t0 = time.monotonic()
        handle = ac.save(1, state)
        print(f"async blocked:            {ac.blocked_s[0]:.2f}s "
              f"(snapshot only; sharded HDD write is in flight)")
        handle.result()  # the future-like handle: block = drain
        print(f"background write finished at t={time.monotonic()-t0:.2f}s")
        restored = ac.restore_pytree(state)
        ok = all(np.array_equal(restored["params"][k], state["params"][k])
                 for k in state["params"])
        print(f"async checkpoint bit-identical: {ok}")
        ac.close()

        afast = make_storage("optane", os.path.join(root, "abb_fast"),
                             time_scale=ts)
        aslow = make_storage("hdd", os.path.join(root, "abb_slow"),
                             time_scale=ts)
        abb = CheckpointManager(aslow, "abb/m", engine="asyncbb",
                                fast_storage=afast, n_shards=4,
                                drain_streams=4)
        t0 = time.monotonic()
        handle = abb.save(1, state)
        print(f"async-bb blocked:         {abb.blocked_s[0]:.2f}s "
              f"(snapshot only; Optane stage + HDD drain in flight)")
        handle.result()   # settles when the *fast* tier has committed
        print(f"fast-tier commit at t={time.monotonic()-t0:.2f}s "
              f"(step already restorable)")
        abb.wait()        # additionally drains the slow tier
        print(f"slow-tier drain finished at t={time.monotonic()-t0:.2f}s")
        restored = CheckpointSaver(aslow, "abb/m").restore_pytree(state)
        ok = all(np.array_equal(restored["params"][k], state["params"][k])
                 for k in state["params"])
        print(f"async-bb slow-tier copy bit-identical: {ok}")
        abb.close()


if __name__ == "__main__":
    main()
