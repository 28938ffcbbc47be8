"""Pallas sqround kernel: bit-exactness vs oracle + statistical properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_shim import given, settings, st

from repro.kernels.sqround.ops import sqround
from repro.kernels.sqround.ref import sqround_ref, uniform01_from_bits
from repro.quant import BY_BITS

BITS = [2, 4, 8]


class TestSqroundVsOracle:
    @given(
        bits=st.sampled_from(BITS),
        r=st.integers(1, 70),
        c=st.integers(1, 90),
        seed=st.integers(0, 999),
    )
    @settings(max_examples=25, deadline=None)
    def test_bit_exact_sweep(self, bits, r, c, seed):
        key = jax.random.PRNGKey(seed)
        v = jax.random.normal(key, (r, c), jnp.float32) * 3.0
        c_pal, s_pal = sqround(v, bits, key, use_pallas=True, interpret=True)
        c_ref, s_ref = sqround(v, bits, key, use_pallas=False)
        np.testing.assert_array_equal(np.asarray(c_pal), np.asarray(c_ref))
        assert float(s_pal) == float(s_ref)

    @pytest.mark.parametrize("bits", BITS)
    def test_bit_exact_across_padded_tiles(self, bits):
        # wider and taller than one (256, 512) tile: both axes are padded
        key = jax.random.PRNGKey(7)
        v = jax.random.normal(key, (300, 600), jnp.float32) * 3.0
        c_pal, _ = sqround(v, bits, key, use_pallas=True, interpret=True)
        c_ref, _ = sqround(v, bits, key, use_pallas=False)
        np.testing.assert_array_equal(np.asarray(c_pal), np.asarray(c_ref))

    @pytest.mark.parametrize("bits", BITS)
    def test_codes_in_range(self, bits):
        key = jax.random.PRNGKey(1)
        v = jax.random.normal(key, (64, 64), jnp.float32)
        codes, _ = sqround(v, bits, key, use_pallas=True, interpret=True)
        k = BY_BITS[bits].half_steps
        assert codes.dtype == jnp.int8
        assert int(codes.max()) <= k and int(codes.min()) >= -k


class TestStatistics:
    def test_unbiased(self):
        """E[dequant(sqround(v))] == v across many keys (2-bit, harshest)."""
        v = jax.random.uniform(jax.random.PRNGKey(2), (8, 8), minval=-1, maxval=1)
        k = BY_BITS[2].half_steps

        def deq(key):
            codes, scale = sqround(v, 2, key, use_pallas=False)
            return codes.astype(jnp.float32) * scale / k

        keys = jax.random.split(jax.random.PRNGKey(3), 3000)
        mean = jax.vmap(deq)(keys).mean(0)
        np.testing.assert_allclose(np.asarray(mean), np.asarray(v), atol=0.08)

    def test_uniform01_range(self):
        u = jax.random.bits(jax.random.PRNGKey(4), (1000,), dtype=jnp.uint32)
        f = uniform01_from_bits(u)
        assert float(f.min()) >= 0.0 and float(f.max()) < 1.0

    def test_explicit_scale(self):
        v = jnp.full((4, 4), 0.5, jnp.float32)
        codes, scale = sqround(v, 8, jax.random.PRNGKey(5), scale=jnp.float32(1.0))
        assert float(scale) == 1.0
        k = BY_BITS[8].half_steps
        np.testing.assert_allclose(np.asarray(codes).astype(float) / k, 0.5, atol=1 / k)
