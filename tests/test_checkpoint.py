"""Checkpoint saver: roundtrip, retention, atomic commit, int8, elastic,
and the shard format byte for byte."""
import io
import json

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # bare env (see `test` extra in pyproject.toml)
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core.checkpoint import (
    CheckpointSaver, dequantize_blockwise, quantize_blockwise, resolve_dtype,
)
from repro.core.recovery import CheckpointManager
from repro.core.storage import NativeStorage


def tree():
    rng = np.random.default_rng(0)
    return {
        "layer0": {"w": rng.normal(size=(32, 16)).astype(np.float32),
                   "b": np.zeros(16, np.float32)},
        "embed": rng.normal(size=(100, 8)).astype(np.float32),
        "step": np.int32(5),
    }


class TestRoundtrip:
    def test_bit_exact(self, tmp_storage):
        t = tree()
        saver = CheckpointSaver(tmp_storage, "ckpt/m", n_shards=3)
        saver.save(10, t)
        out = saver.restore_pytree(t)
        for a, b in zip(
            [t["layer0"]["w"], t["layer0"]["b"], t["embed"], t["step"]],
            [out["layer0"]["w"], out["layer0"]["b"], out["embed"], out["step"]],
        ):
            np.testing.assert_array_equal(a, b)

    def test_shard_layout(self, tmp_storage):
        saver = CheckpointSaver(tmp_storage, "ckpt/m", n_shards=4)
        r = saver.save(1, tree())
        data_files = [f for f in r.files if ".data-" in f]
        assert len(data_files) == 4
        assert tmp_storage.exists("ckpt/m-1.index")
        assert tmp_storage.exists("ckpt/m-1.meta")

    def test_restore_specific_step(self, tmp_storage):
        saver = CheckpointSaver(tmp_storage, "ckpt/m")
        t = tree()
        saver.save(1, t)
        t2 = {k: (v if not isinstance(v, dict) else v) for k, v in t.items()}
        t2["embed"] = t["embed"] * 2
        saver.save(2, t2)
        old = saver.restore_pytree(t, step=1)
        np.testing.assert_array_equal(old["embed"], t["embed"])


class TestExtensionDtypes:
    def test_resolve_dtype_builtin_and_extension(self):
        assert resolve_dtype("float32") == np.dtype(np.float32)
        import ml_dtypes
        assert resolve_dtype("bfloat16") == np.dtype(ml_dtypes.bfloat16)
        with pytest.raises(TypeError):
            resolve_dtype("not_a_dtype")

    def test_bfloat16_roundtrip(self, tmp_storage):
        """Restore of bfloat16 leaves must not depend on np.dtype('bfloat16')
        being registered (it raises unless ml_dtypes was imported)."""
        import jax.numpy as jnp

        t = {"w": jnp.arange(64, dtype=jnp.bfloat16).reshape(8, 8),
             "b": np.ones(8, np.float32)}
        saver = CheckpointSaver(tmp_storage, "ckpt/m", n_shards=2)
        saver.save(1, t)
        out = saver.restore_pytree(t)
        assert str(out["w"].dtype) == "bfloat16"
        np.testing.assert_array_equal(
            np.asarray(out["w"], np.float32), np.asarray(t["w"], np.float32))

    def test_bfloat16_quantized_save_does_not_crash(self, tmp_storage):
        import jax.numpy as jnp

        t = {"w": jnp.ones((512,), jnp.bfloat16)}
        saver = CheckpointSaver(tmp_storage, "ckpt/m", quantize="int8")
        saver.save(1, t)
        out = saver.restore_pytree(t)
        assert str(out["w"].dtype) == "bfloat16"
        np.testing.assert_allclose(
            np.asarray(out["w"], np.float32), np.ones(512, np.float32),
            atol=0.02)


class TestRetention:
    def test_keep_n(self, tmp_storage):
        saver = CheckpointSaver(tmp_storage, "ckpt/m", keep=2)
        t = tree()
        for s in (10, 20, 30, 40):
            saver.save(s, t)
        assert saver.all_steps() == [30, 40]
        files = tmp_storage.listdir("ckpt")
        assert not any(f.startswith("m-10.") or f.startswith("m-20.") for f in files)
        with pytest.raises(FileNotFoundError):
            saver.restore(step=10)


class TestAtomicity:
    def test_crash_before_marker_keeps_previous(self, tmp_storage):
        saver = CheckpointSaver(tmp_storage, "ckpt/m")
        t = tree()
        saver.save(1, t)
        # simulate crash mid-save of step 2: data written, marker not updated
        base = "ckpt/m-2"
        tmp_storage.write_file(f"{base}.data-00000-of-00001", b"garbage")
        # marker still points at step 1
        assert saver.latest_step() == 1
        out = saver.restore_pytree(t)
        np.testing.assert_array_equal(out["embed"], t["embed"])


class TestQuantized:
    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=20, deadline=None)
    def test_q8_roundtrip_error_bound(self, seed):
        rng = np.random.default_rng(seed)
        x = (rng.normal(size=(777,)) * rng.uniform(0.1, 100)).astype(np.float32)
        q, s, pad = quantize_blockwise(x)
        back = dequantize_blockwise(q, s, pad, x.shape, np.float32)
        # absmax/127 per block bounds the error
        blocks = np.pad(x, (0, pad)).reshape(-1, 256)
        bound = (np.abs(blocks).max(axis=1, keepdims=True) / 127.0) * 0.5 + 1e-7
        err = np.abs(np.pad(x, (0, pad)).reshape(-1, 256) - np.pad(back, (0, pad)).reshape(-1, 256))
        assert (err <= bound + 1e-6).all()

    def test_int8_checkpoint_smaller_and_close(self, tmp_storage):
        t = {"w": np.random.default_rng(0).normal(size=(512, 256)).astype(np.float32)}
        full = CheckpointSaver(tmp_storage, "full/m")
        q8 = CheckpointSaver(tmp_storage, "q8/m", quantize="int8")
        rf = full.save(1, t)
        rq = q8.save(1, t)
        assert rq.n_bytes < rf.n_bytes * 0.35
        out = q8.restore_pytree(t)
        rel = np.abs(out["w"] - t["w"]).max() / np.abs(t["w"]).max()
        assert rel < 0.02


class TestElastic:
    def test_restore_sharded_roundtrip_1dev(self, tmp_storage):
        """Elastic restore path (single device: trivial mesh)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        t = {"w": np.arange(64, dtype=np.float32).reshape(8, 8)}
        saver = CheckpointSaver(tmp_storage, "ckpt/m")
        saver.save(3, t)
        mesh = jax.make_mesh((1,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        sh = {"w": NamedSharding(mesh, P("data", None))}
        out = saver.restore_sharded(t, sh)
        np.testing.assert_array_equal(np.asarray(out["w"]), t["w"])


# ---------------------------------------------------------------------------
# the shard format, byte for byte
# ---------------------------------------------------------------------------
def format_leaves():
    """One leaf of each layout the serializer has to lay out as
    ``tobytes()`` does; the float ones hold 256 elements or more, so int8
    quantizes them (with a padded last block where the size asks for it)."""
    import ml_dtypes

    rng = np.random.default_rng(7)
    wide = rng.normal(size=(40, 60)).astype(np.float32)
    leaves = {
        "f32": rng.normal(size=(19, 31)).astype(np.float32),
        "bf16": rng.normal(size=(300,)).astype(ml_dtypes.bfloat16),
        "int32": rng.integers(-2**31, 2**31 - 1, size=(7, 45), dtype=np.int32),
        "scalar": np.array(3.5, np.float32),
        "empty": np.zeros((0, 5), np.float32),
        "fortran": np.asfortranarray(
            rng.normal(size=(24, 33)).astype(np.float32)),
        "strided": wide[::2, ::3],
    }
    assert not leaves["fortran"].flags["C_CONTIGUOUS"]
    assert not leaves["strided"].flags["C_CONTIGUOUS"]
    return leaves


def tobytes_shards(flat, n_shards, quantize):
    """The format as ``tobytes()`` into one ``BytesIO`` per shard wrote it:
    the reference the serializer's shards and index must equal."""
    names = sorted(flat, key=lambda k: -flat[k].nbytes)
    shard_of, shard_bytes = {}, [0] * n_shards
    for name in names:
        s = int(np.argmin(shard_bytes))
        shard_of[name] = s
        shard_bytes[s] += flat[name].nbytes
    buffers = [io.BytesIO() for _ in range(n_shards)]
    index = {}
    for name, arr in flat.items():
        buf = buffers[shard_of[name]]
        entry = dict(shard=shard_of[name], offset=buf.tell(),
                     shape=list(arr.shape), dtype=str(arr.dtype))
        if (quantize == "int8" and str(arr.dtype) in
                ("float32", "float64", "bfloat16") and arr.size >= 256):
            q, scale, pad = quantize_blockwise(arr)
            buf.write(q.tobytes())
            entry.update(quant="int8", qpad=pad, qblock=256,
                         scale_offset=buf.tell(), scale_len=scale.nbytes)
            buf.write(scale.tobytes())
            entry["length"] = buf.tell() - entry["offset"]
        else:
            data = arr.tobytes()
            buf.write(data)
            entry["length"] = len(data)
        index[name] = entry
    return [b.getvalue() for b in buffers], index


FORMAT_CASES = ["f32", "bf16", "int32", "scalar", "empty", "fortran",
                "strided", "all"]


class TestShardFormat:
    @pytest.mark.parametrize("quantize", [None, "int8"])
    @pytest.mark.parametrize("n_shards", [1, 3])
    @pytest.mark.parametrize("leaf", FORMAT_CASES)
    def test_shards_and_index_equal_tobytes_layout(self, tmp_storage, leaf,
                                                   n_shards, quantize):
        leaves = format_leaves()
        flat = leaves if leaf == "all" else {leaf: leaves[leaf]}
        want_shards, want_index = tobytes_shards(flat, n_shards, quantize)
        saver = CheckpointSaver(tmp_storage, "ckpt/m", n_shards=n_shards,
                                quantize=quantize, sync=False)
        result = saver.save_flat(1, flat)
        got = [tmp_storage.read_file(f"ckpt/m-1.data-{s:05d}-of-{n_shards:05d}")
               for s in range(n_shards)]
        assert got == want_shards
        index_blob = json.dumps(
            dict(tensors=want_index, n_shards=n_shards)).encode()
        assert tmp_storage.read_file("ckpt/m-1.index") == index_blob
        meta_len = len(tmp_storage.read_file("ckpt/m-1.meta"))
        assert result.n_bytes == (sum(map(len, want_shards))
                                  + len(index_blob) + meta_len)

    @pytest.mark.parametrize("engine", ["direct", "async", "bb", "asyncbb"])
    def test_manager_resume_is_bit_exact(self, engine, tmp_path):
        leaves = format_leaves()
        state = {"params": {k: v for k, v in leaves.items()
                            if k not in ("scalar", "int32")},
                 "step": leaves["scalar"], "rng": leaves["int32"]}
        slow = NativeStorage(str(tmp_path / "slow"))
        fast = NativeStorage(str(tmp_path / "fast"))
        mgr = CheckpointManager(slow, "ckpt/m", engine=engine,
                                fast_storage=fast, n_shards=3)
        mgr.save(4, state)
        mgr.wait()
        mgr.close()
        skeleton = {"params": {k: np.zeros_like(v)
                               for k, v in state["params"].items()},
                    "step": np.zeros_like(state["step"]),
                    "rng": np.zeros_like(state["rng"])}
        fresh = CheckpointManager(slow, "ckpt/m", n_shards=3)
        res = fresh.resume(skeleton)
        fresh.close()
        assert res.step == 4
        want = dict(state["params"], step=state["step"], rng=state["rng"])
        got = dict(res.state["params"], step=res.state["step"],
                   rng=res.state["rng"])
        assert set(got) == set(want)
        for name, arr in want.items():
            out = np.asarray(got[name])
            assert out.dtype == arr.dtype and out.shape == arr.shape, name
            assert out.tobytes() == arr.tobytes(), name
