"""Quickstart: train a tiny LM end-to-end with the full I/O stack.

    PYTHONPATH=src python examples/quickstart.py

Data flows through the paper's pipeline (parallel map + shuffle + batch +
prefetch), training checkpoints through a burst buffer (fast tier + async
drain), and the run resumes from the newest checkpoint if re-run.
"""
import sys, tempfile, os
sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS
from repro.core import CheckpointManager, Dataset, make_storage
from repro.core import records
from repro.launch.compile_cache import use_compile_cache
from repro.train import steps as S
from repro.train.optimizer import OptConfig
from repro.train.trainer import Trainer


def main():
    use_compile_cache()
    cfg = ARCHS["qwen3-4b"].smoke()
    opt = OptConfig(lr=3e-3)
    root = tempfile.mkdtemp()

    # 1. corpus on a simulated SSD tier
    data_st = make_storage("ssd", os.path.join(root, "data"), time_scale=0.05)
    shards = records.write_token_dataset(
        data_st, n_shards=8, docs_per_shard=16, seq_len=33,
        vocab_size=cfg.vocab_size)

    # 2. the paper's input pipeline: shuffle -> parallel read/decode -> batch -> prefetch
    def load(path):
        return records.decode_token_shard(data_st.read_file(path), 33)

    ds = (Dataset.from_tensor_slices(shards)
          .repeat()
          .shuffle(8, seed=0)
          .map(load, num_parallel_calls=4)
          .prefetch(2))

    def batches():
        for shard in ds:
            for i in range(0, len(shard), 4):
                yield {"tokens": jnp.asarray(shard[i:i + 4])}

    # 3. burst-buffer checkpointing: optane stage, hdd archive
    fast = make_storage("optane", os.path.join(root, "bb"), time_scale=0.05)
    slow = make_storage("hdd", os.path.join(root, "archive"), time_scale=0.05)
    ckpt = CheckpointManager(slow, "ckpt/quickstart", engine="bb",
                             fast_storage=fast)

    # 4. train
    state = S.init_train_state(jax.random.PRNGKey(0), cfg, opt)
    step = jax.jit(S.make_train_step(cfg, opt, None, remat=False,
                                     q_chunk=16, kv_chunk=16))
    tr = Trainer(step, state, batches(), checkpointer=ckpt, ckpt_every=5)
    hist = tr.run(15)
    ckpt.wait()
    print(f"step {tr.step}: loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}")
    print("report:", {k: v for k, v in tr.report().items() if k != 'timer'})
    print("archived checkpoint steps on slow tier:",
          [d.step for d in ckpt.engine.drains])
    ckpt.close()


if __name__ == "__main__":
    main()
