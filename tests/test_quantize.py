"""Unit + property tests for the stochastic quantizer and bit packing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_shim import given, settings, st

from repro.quant import (
    BY_BITS,
    QuantFormat,
    fake_quantize,
    pack_codes,
    packed_len,
    quantize,
    quantize_codes,
    unpack_codes,
)

BITS = [2, 4, 8]


class TestFormats:
    @pytest.mark.parametrize("bits,levels,k", [(2, 3, 1), (4, 9, 4), (8, 129, 64)])
    def test_odd_levels(self, bits, levels, k):
        f = QuantFormat(bits)
        assert f.levels == levels
        assert f.half_steps == k
        assert f.code_min == -k and f.code_max == k

    def test_invalid_bits(self):
        with pytest.raises(ValueError):
            QuantFormat(3)

    @pytest.mark.parametrize("bits", BITS)
    def test_lemma4_bound_formula(self, bits):
        f = BY_BITS[bits]
        assert f.expected_error_bound(1.0, 4) == pytest.approx(2.0 / 2 ** (bits - 1))


class TestQuantize:
    @pytest.mark.parametrize("bits", BITS)
    def test_codes_in_range(self, bits):
        v = jax.random.normal(jax.random.PRNGKey(0), (257,))
        codes, scale = quantize_codes(v, bits, jax.random.PRNGKey(1))
        k = BY_BITS[bits].half_steps
        assert int(jnp.max(codes)) <= k and int(jnp.min(codes)) >= -k

    @pytest.mark.parametrize("bits", BITS)
    def test_elementwise_error_bounds(self, bits):
        """Stochastic rounding moves at most one full step Delta = scale/K;
        nearest rounding at most Delta/2 = scale/2^(b-1) (Lemma 4's expected
        bound is the nearest-rounding worst case)."""
        v = jax.random.normal(jax.random.PRNGKey(2), (513,))
        scale = float(jnp.max(jnp.abs(v)))
        k = BY_BITS[bits].half_steps
        d_sto = fake_quantize(v, bits, jax.random.PRNGKey(3))
        assert float(jnp.max(jnp.abs(d_sto - v))) <= scale / k + 1e-6
        d_det = fake_quantize(v, bits, key=None)
        assert float(jnp.max(jnp.abs(d_det - v))) <= scale / (2 * k) + 1e-6

    def test_unbiased(self):
        """E[Q_b(v)] = v  (statistical, 2-bit is the harshest)."""
        v = jax.random.uniform(jax.random.PRNGKey(4), (32,), minval=-1, maxval=1)
        keys = jax.random.split(jax.random.PRNGKey(5), 4000)
        mean = jax.vmap(lambda k: fake_quantize(v, 2, k))(keys).mean(0)
        # std of mean ~ scale/sqrt(n) ~ 1/63 -> 5 sigma
        np.testing.assert_allclose(np.asarray(mean), np.asarray(v), atol=0.08)

    def test_deterministic_is_nearest(self):
        v = jnp.asarray([0.0, 0.24, 0.26, -0.6, 1.0])
        d = fake_quantize(v, 4, key=None, scale=jnp.asarray(1.0))
        np.testing.assert_allclose(np.asarray(d), [0.0, 0.25, 0.25, -0.5, 1.0], atol=1e-6)

    def test_zero_exactly_representable(self):
        v = jnp.zeros((16,))
        for bits in BITS:
            d = fake_quantize(v, bits, jax.random.PRNGKey(0))
            assert float(jnp.max(jnp.abs(d))) == 0.0

    def test_complex_roundtrip(self):
        key = jax.random.PRNGKey(6)
        v = (
            jax.random.normal(key, (64,)) + 1j * jax.random.normal(jax.random.fold_in(key, 1), (64,))
        ).astype(jnp.complex64)
        q = quantize(v, 8, key)
        d = q.dequantize()
        assert d.dtype == jnp.complex64
        scale = float(q.scale)
        # stochastic rounding: at most one step (scale/K, K=64 for 8 bits)
        assert float(jnp.max(jnp.abs(jnp.real(d - v)))) <= scale / 64 + 1e-6
        assert float(jnp.max(jnp.abs(jnp.imag(d - v)))) <= scale / 64 + 1e-6

    def test_per_channel_scale(self):
        v = jnp.stack([jnp.ones(8) * 0.001, jnp.ones(8) * 100.0])
        q = quantize(v, 8, channel_axis=0)
        d = q.dequantize()
        np.testing.assert_allclose(np.asarray(d), np.asarray(v), rtol=0.02)

    def test_qtensor_is_pytree(self):
        q = quantize(jnp.ones((4,)), 4, jax.random.PRNGKey(0))
        leaves = jax.tree_util.tree_leaves(q)
        assert len(leaves) == 2
        # jaxlint: allow=JL006 -- one-shot jit: the test IS the trace-through
        out = jax.jit(lambda t: t.dequantize())(q)
        assert out.shape == (4,)


class TestPacking:
    @given(
        bits=st.sampled_from(BITS),
        n=st.integers(min_value=1, max_value=67),
        rows=st.integers(min_value=1, max_value=5),
    )
    @settings(max_examples=60, deadline=None)
    def test_pack_roundtrip(self, bits, n, rows):
        key = jax.random.PRNGKey(n * 7 + rows)
        v = jax.random.normal(key, (rows, n))
        codes, _ = quantize_codes(v, bits, key)
        packed = pack_codes(codes, bits)
        assert packed.shape == (rows, packed_len(n, bits))
        assert packed.dtype == jnp.uint8
        un = unpack_codes(packed, bits, n)
        np.testing.assert_array_equal(np.asarray(un), np.asarray(codes))

    @pytest.mark.parametrize("bits,ratio", [(2, 4), (4, 2), (8, 1)])
    def test_compression_ratio(self, bits, ratio):
        assert packed_len(128, bits) == 128 // ratio


class TestDtypeRoundTrip:
    """ISSUE-4 regression: fake_quantize must preserve the input dtype —
    complex128 measurements were silently narrowed to complex64 (dequantize
    built lax.complex from f32 parts and fake_quantize requested no dtype
    for complex inputs)."""

    @pytest.mark.parametrize("dt", ["float32", "float64", "complex64", "complex128"])
    def test_fake_quantize_preserves_dtype(self, dt):
        with jax.enable_x64():
            dtype = jnp.dtype(dt)
            v = jnp.asarray([0.5, -0.25, 1.0, 0.0], dtype)
            if jnp.issubdtype(dtype, jnp.complexfloating):
                v = v * (1.0 + 0.5j)
            out = fake_quantize(v, 8, jax.random.PRNGKey(0))
            assert out.dtype == dtype
            # values still within one quantization step
            step = float(jnp.max(jnp.abs(v))) / BY_BITS[8].half_steps
            assert float(jnp.max(jnp.abs(out - v))) <= step

    def test_complex128_explicit_f32_scale(self):
        """The narrowing path: an f32 scale must not drag the output to c64."""
        with jax.enable_x64():
            v = jnp.asarray([0.5 + 0.5j, -0.25 - 1.0j], jnp.complex128)
            q = quantize(v, 8, jax.random.PRNGKey(1), scale=jnp.float32(1.0))
            assert q.dequantize(jnp.complex128).dtype == jnp.complex128
            out = fake_quantize(v, 8, jax.random.PRNGKey(1),
                                scale=jnp.float32(1.0))
            assert out.dtype == jnp.complex128

    def test_default_x64_disabled_unchanged(self):
        v = (jax.random.normal(jax.random.PRNGKey(2), (16,))
             + 1j * jax.random.normal(jax.random.PRNGKey(3), (16,))
             ).astype(jnp.complex64)
        out = fake_quantize(v, 4, jax.random.PRNGKey(4))
        assert out.dtype == jnp.complex64
