"""Multi-device tests (subprocesses: device count is locked at jax init,
and the main test session must keep seeing 1 CPU device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_ENV = dict(os.environ,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=8",
            PYTHONPATH="src")

# prepended to every subprocess: meshes with Auto axes, so shardings
# propagate through jit as in the library's own steps
_PREAMBLE = textwrap.dedent("""
    import jax as _jax_compat

    def make_mesh(shape, names):
        return _jax_compat.make_mesh(
            shape, names,
            axis_types=(_jax_compat.sharding.AxisType.Auto,) * len(shape))
""")


def run_py(code: str, timeout=600) -> str:
    r = subprocess.run([sys.executable, "-c",
                        _PREAMBLE + textwrap.dedent(code)],
                       env=_ENV, capture_output=True, text=True,
                       timeout=timeout, cwd=os.getcwd())
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    return r.stdout


@pytest.mark.slow
class TestDistributed:
    def test_sharded_train_step_runs_and_learns(self):
        out = run_py("""
            import jax, jax.numpy as jnp, json
            from repro.configs import ARCHS
            from repro.sharding.rules import ShardingCtx
            from repro.train import steps as S
            from repro.train.optimizer import OptConfig

            mesh = make_mesh((2,2,2), ("pod","data","model"))
            cfg = ARCHS["qwen3-4b"].smoke()
            opt = OptConfig()
            ctx = ShardingCtx(mesh=mesh)
            rng = jax.random.PRNGKey(0)
            shapes = jax.eval_shape(lambda: S.init_train_state(rng, cfg, opt))
            st_sh = S.state_shardings(cfg, ctx, shapes)
            state = jax.jit(lambda: S.init_train_state(rng, cfg, opt),
                            out_shardings=st_sh)()
            toks = jax.random.randint(rng, (8, 33), 0, cfg.padded_vocab,
                                      dtype=jnp.int32)
            b_sh = S.batch_shardings(cfg, ctx, {"tokens": toks})
            step = jax.jit(S.make_train_step(cfg, opt, ctx, q_chunk=16,
                                             kv_chunk=16),
                           in_shardings=(st_sh, b_sh),
                           out_shardings=(st_sh, None))
            with mesh:
                losses = []
                for _ in range(4):
                    state, m = step(state, {"tokens": toks})
                    losses.append(float(m["loss"]))
            print(json.dumps(losses))
        """)
        losses = json.loads(out.strip().splitlines()[-1])
        assert losses[-1] < losses[0]

    def test_compressed_allreduce_matches_mean(self):
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.train.compress import compressed_allreduce_stacked
            mesh = make_mesh((2,2,2), ("pod","data","model"))
            x = jax.random.normal(jax.random.PRNGKey(0), (2, 4096)) * 3
            with mesh:
                out = compressed_allreduce_stacked(mesh, x)
            ref = np.asarray(x).mean(0)
            rel = float(np.abs(np.asarray(out) - ref).max() / np.abs(ref).max())
            assert rel < 0.02, rel
            print("REL", rel)
        """)
        assert "REL" in out

    def test_elastic_restore_across_topologies(self, tmp_path):
        """Save on a (4,2) mesh layout, restore onto (2,4) — the index is
        topology-free."""
        ckpt_dir = str(tmp_path)
        run_py(f"""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.core.storage import NativeStorage
            from repro.core.checkpoint import CheckpointSaver
            mesh = make_mesh((4,2), ("data","model"))
            w = jnp.arange(64*32, dtype=jnp.float32).reshape(64, 32)
            w = jax.device_put(w, NamedSharding(mesh, P("data","model")))
            saver = CheckpointSaver(NativeStorage({ckpt_dir!r}), "ckpt/m")
            saver.save(1, {{"w": w}})
        """)
        out = run_py(f"""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.core.storage import NativeStorage
            from repro.core.checkpoint import CheckpointSaver
            mesh = make_mesh((2,4), ("data","model"))
            saver = CheckpointSaver(NativeStorage({ckpt_dir!r}), "ckpt/m")
            skeleton = {{"w": np.zeros((64,32), np.float32)}}
            sh = {{"w": NamedSharding(mesh, P("data","model"))}}
            out = saver.restore_sharded(skeleton, sh)
            expect = np.arange(64*32, dtype=np.float32).reshape(64,32)
            np.testing.assert_array_equal(np.asarray(out["w"]), expect)
            print("ELASTIC OK", out["w"].sharding)
        """)
        assert "ELASTIC OK" in out
