"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # bare env (see `test` extra in pyproject.toml)
    from _hypothesis_fallback import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels import preprocess as _kpre
from repro.kernels.quantize import BLOCK, dequantize_blocks, quantize_blocks


class TestQuantizeKernel:
    @pytest.mark.parametrize("n", [1, 7, 256, 300])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, n, dtype):
        x = (jax.random.normal(jax.random.PRNGKey(n), (n, BLOCK)) * 5).astype(dtype)
        q, s = quantize_blocks(x)
        qr, sr = ref.quantize_blocks_ref(x)
        # last-ulp division differences (compiled vs interpret) may flip a
        # value sitting exactly on a rounding boundary by 1 level
        dq = np.abs(np.asarray(q, np.int32) - np.asarray(qr, np.int32))
        assert dq.max() <= 1
        # rate bound, with an absolute floor so a single boundary flip in a
        # small array (1 block = 256 values) doesn't trip it
        assert (dq > 0).sum() <= max(1, dq.size // 1000)
        np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-5)
        back = dequantize_blocks(q, s)
        br = ref.dequantize_blocks_ref(qr, sr)
        np.testing.assert_allclose(np.asarray(back), np.asarray(br),
                                   rtol=1e-5, atol=float(np.asarray(s).max()))

    def test_zero_block_scale_is_one(self):
        x = jnp.zeros((4, BLOCK))
        q, s = quantize_blocks(x)
        assert (np.asarray(s) == 1.0).all()
        assert (np.asarray(q) == 0).all()

    @given(st.integers(0, 10_000), st.floats(0.01, 1e4))
    @settings(max_examples=20, deadline=None)
    def test_property_roundtrip_bound(self, seed, scale):
        x = jax.random.normal(jax.random.PRNGKey(seed), (31,)) * scale
        q, s = ops.quantize(x)
        back = ops.dequantize(q, s, x.shape)
        bound = np.abs(np.asarray(x)).max() / 127.0 * 0.5 + 1e-9
        assert np.abs(np.asarray(back) - np.asarray(x)).max() <= bound * 1.01

    def test_any_shape_wrapper(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 7))
        q, s = ops.quantize(x)
        back = ops.dequantize(q, s, x.shape)
        assert back.shape == x.shape


class TestPreprocessKernel:
    @pytest.mark.parametrize("hw", [(8, 8), (17, 23), (64, 48)])
    @pytest.mark.parametrize("c", [1, 3])
    def test_matches_ref(self, hw, c):
        h, w = hw
        img = jax.random.randint(jax.random.PRNGKey(0), (2, h, w, c), 0, 256,
                                 dtype=jnp.uint8)
        mean = jnp.linspace(0.3, 0.6, c)
        std = jnp.linspace(0.2, 0.3, c)
        out = ops.normalize_images_nhwc(img, mean, std)
        xc = jnp.transpose(img, (0, 3, 1, 2)).reshape(2, c, h * w)
        r = ref.normalize_images_ref(xc, mean, std)
        r = jnp.transpose(r.reshape(2, c, h, w), (0, 2, 3, 1))
        np.testing.assert_allclose(np.asarray(out), np.asarray(r),
                                   rtol=1e-4, atol=1e-5)


class TestResizeConvertKernel:
    @pytest.mark.parametrize("in_hw,out_hw", [
        ((24, 20), (12, 16)), ((9, 13), (17, 8)), ((16, 16), (16, 16)),
    ])
    @pytest.mark.parametrize("c", [1, 3])
    def test_pallas_matches_numpy_fallback(self, in_hw, out_hw, c):
        rng = np.random.default_rng(sum(in_hw + out_hw))
        x = rng.integers(0, 256, (3, *in_hw, c), dtype=np.uint8)
        got = np.asarray(_kpre.resize_convert_images(
            jnp.asarray(x), *out_hw))
        want = _kpre.resize_convert_batch_np(x, *out_hw)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_pallas_matches_jnp_oracle(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.integers(0, 256, (2, 14, 18, 3), dtype=np.uint8))
        got = _kpre.resize_convert_images(x, 7, 9)
        want = ref.resize_convert_ref(x, 7, 9)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)

    def test_matches_per_image_host_path(self):
        from repro.core import records

        rng = np.random.default_rng(1)
        x = rng.integers(0, 256, (4, 20, 16, 3), dtype=np.uint8)
        got = np.asarray(_kpre.resize_convert_images(jnp.asarray(x), 10, 8))
        per_image = np.stack([
            records.preprocess_image(records.encode_image(x[i]), 10, 8)
            for i in range(4)
        ])
        np.testing.assert_allclose(got, per_image, rtol=1e-5, atol=1e-5)

    def test_float_and_uint16_inputs(self):
        rng = np.random.default_rng(2)
        xf = rng.random((2, 10, 12, 1)).astype(np.float32)
        np.testing.assert_allclose(
            np.asarray(_kpre.resize_convert_images(jnp.asarray(xf), 5, 6)),
            _kpre.resize_convert_batch_np(xf, 5, 6), rtol=1e-5, atol=1e-6)
        xu = rng.integers(0, 65536, (2, 10, 12, 1)).astype(np.uint16)
        got = np.asarray(_kpre.resize_convert_images(jnp.asarray(xu), 5, 6))
        assert got.min() >= 0.0 and got.max() <= 1.0

    def test_dispatcher_backends_agree(self):
        rng = np.random.default_rng(3)
        x = rng.integers(0, 256, (2, 12, 12, 3), dtype=np.uint8)
        a = np.asarray(_kpre.resize_convert(x, 6, 6, backend="numpy"))
        b = np.asarray(_kpre.resize_convert(x, 6, 6, backend="pallas"))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
        with pytest.raises(ValueError):
            _kpre.resize_convert(x, 6, 6, backend="tpu2000")

    def test_jit_wrapper(self):
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.integers(0, 256, (2, 10, 10, 3), dtype=np.uint8))
        out = ops.resize_convert_nhwc(x, 5, 5)
        assert out.shape == (2, 5, 5, 3) and out.dtype == jnp.float32


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("sq,skv,bq,bk", [
        (128, 128, 64, 64), (256, 256, 128, 64), (64, 64, 64, 64),
    ])
    @pytest.mark.parametrize("hd", [32, 64])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref(self, sq, skv, bq, bk, hd, causal):
        key = jax.random.PRNGKey(hd + sq)
        kq, kk, kv_ = jax.random.split(key, 3)
        q = jax.random.normal(kq, (2, sq, 4, hd), jnp.float32)
        k = jax.random.normal(kk, (2, skv, 2, hd), jnp.float32)
        v = jax.random.normal(kv_, (2, skv, 2, hd), jnp.float32)
        o = ops.flash_attention_bhsd(q, k, v, causal=causal, bq=bq, bk=bk)
        kb = jnp.repeat(k, 2, axis=2)
        vb = jnp.repeat(v, 2, axis=2)
        qf = q.transpose(0, 2, 1, 3).reshape(8, sq, hd)
        kf = kb.transpose(0, 2, 1, 3).reshape(8, skv, hd)
        vf = vb.transpose(0, 2, 1, 3).reshape(8, skv, hd)
        orf = ref.attention_ref(qf, kf, vf, causal=causal)
        orf = orf.reshape(2, 4, sq, hd).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                                   atol=3e-5, rtol=1e-3)

    def test_bf16_io(self):
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 2, 32), jnp.bfloat16)
        k = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 2, 32), jnp.bfloat16)
        v = jax.random.normal(jax.random.PRNGKey(2), (1, 64, 2, 32), jnp.bfloat16)
        o = ops.flash_attention_bhsd(q, k, v, bq=64, bk=64)
        assert o.dtype == jnp.bfloat16
        assert np.isfinite(np.asarray(o, np.float32)).all()

    def test_agrees_with_model_chunked_attention(self):
        from repro.models.layers import chunked_attention

        q = jax.random.normal(jax.random.PRNGKey(3), (2, 128, 4, 32))
        k = jax.random.normal(jax.random.PRNGKey(4), (2, 128, 2, 32))
        v = jax.random.normal(jax.random.PRNGKey(5), (2, 128, 2, 32))
        o_kernel = ops.flash_attention_bhsd(q, k, v, bq=64, bk=64)
        o_model = chunked_attention(q, k, v, causal=True, q_chunk=64, kv_chunk=64)
        np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_model),
                                   atol=3e-5, rtol=1e-3)


class TestInterpretDefault:
    def test_cpu_backend_interprets_and_a_bool_wins(self):
        from repro.kernels import resolve_interpret

        assert jax.default_backend() == "cpu"
        assert resolve_interpret(None) is True
        assert resolve_interpret(False) is False
        assert resolve_interpret(True) is True
