"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --smoke \
        --steps 100 --ckpt-every 20 --data-tier ssd --ckpt fast:optane,slow:hdd

At smoke scale this actually trains on CPU (the ~100M-class configuration
the assignment asks for is ``--arch granite-moe-3b-a800m --smoke`` or any
smoke config scaled via --d-model/--layers).  At full scale the same step
function is what repro.launch.dryrun lowers onto the pod meshes.
"""
import argparse
import os
import sys
import tempfile

import jax
import jax.numpy as jnp

from ..configs import ARCHS
from ..core import CheckpointManager, Dataset, make_storage
from ..core import records
from ..train import steps as S
from ..train.optimizer import OptConfig
from ..train.trainer import Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b", choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--data-tier", default="ssd")
    ap.add_argument("--ckpt-fast", default="optane")
    ap.add_argument("--ckpt-slow", default="hdd")
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--threads", type=int, default=4)
    ap.add_argument("--opt-state", default="float32",
                    choices=["float32", "bfloat16", "int8"])
    args = ap.parse_args()

    cfg = ARCHS[args.arch]
    if args.smoke:
        cfg = cfg.smoke()
    opt = OptConfig(lr=args.lr, state_dtype=args.opt_state)
    root = args.workdir or tempfile.mkdtemp(prefix="repro_train_")
    print(f"workdir: {root}")

    data_st = make_storage(args.data_tier, os.path.join(root, "data"),
                           time_scale=0.05)
    shards = records.write_token_dataset(
        data_st, n_shards=8, docs_per_shard=args.batch * 4,
        seq_len=args.seq + 1, vocab_size=cfg.vocab_size)

    def load(path):
        return records.decode_token_shard(data_st.read_file(path), args.seq + 1)

    ds = (Dataset.from_tensor_slices(shards).repeat().shuffle(8, seed=0)
          .map(load, num_parallel_calls=args.threads).prefetch(2))

    def batches():
        for shard in ds:
            for i in range(0, len(shard) - args.batch + 1, args.batch):
                batch = {"tokens": jnp.asarray(shard[i:i + args.batch])}
                if cfg.family == "encdec":
                    batch["frames"] = jnp.zeros(
                        (args.batch, 8, cfg.d_model), jnp.bfloat16)
                yield batch

    fast = make_storage(args.ckpt_fast, os.path.join(root, "bb"), time_scale=0.05)
    slow = make_storage(args.ckpt_slow, os.path.join(root, "archive"),
                        time_scale=0.05)
    ckpt = CheckpointManager(slow, f"ckpt/{cfg.name}", engine="bb",
                             fast_storage=fast)

    state = S.init_train_state(jax.random.PRNGKey(0), cfg, opt)
    step = jax.jit(S.make_train_step(cfg, opt, None, remat=False,
                                     q_chunk=16, kv_chunk=16))
    tr = Trainer(step, state, batches(), checkpointer=ckpt,
                 ckpt_every=args.ckpt_every, install_sigterm=True,
                 on_step=lambda s, m: print(f"step {s}: loss={m['loss']:.4f}")
                 if s % 10 == 0 else None)
    tr.run(args.steps)
    ckpt.wait()
    rep = tr.report()
    print(f"done at step {tr.step}; data-wait {rep['data_wait_frac']:.1%}; "
          f"ckpt blocked {sum(rep['blocked_ckpt_s']):.2f}s")
    ckpt.close()


if __name__ == "__main__":
    main()
