"""Group-scaled quantization end to end: Granularity plumbing, Lemma-4
per-block error bounds, frozen per-tensor fixtures, the group-scaled qmm
kernels, packed-operator granularity, qniht threading, and per-band k-space
observation quantization."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_shim import given, settings, st

from repro.core import qniht, relative_error
from repro.core.operators import PackedStreamingOperator
from repro.kernels.qmm.ops import pack_operator, pack_weights, qmm
from repro.kernels.qmm.ref import qmm_group_ref
from repro.quant import (
    Granularity,
    as_granularity,
    expand_block_scale,
    fake_quantize,
    per_block,
    quantize,
    quantize_codes,
    validate_group_packing,
)
from repro.sensing import (
    kspace_band_scales,
    kspace_radial_bands,
    make_gaussian_problem,
    make_mri_problem,
    quantize_observations,
)

BITS = [2, 4, 8]

# ---------------------------------------------------------------------------
# Frozen fixture: the pre-refactor per-tensor quantizer output for
# jax.random.normal(PRNGKey(42), (24,)). The refactored default path must
# reproduce these codes BIT-IDENTICALLY (nearest and stochastic rounding).
# The codes were recorded under the non-partitionable threefry PRNG (JAX's
# default before 0.5), so the tests draw their random streams under it too.
# ---------------------------------------------------------------------------
_FIXTURE_KEY = 42
_FIXTURE_SCALE = 2.130046844482422
_FROZEN_NEAREST = {
    2: [0, 0, 1, -1, -1, 0, 0, 0, 0, 0, 1, 1, 0, 0, 0, 1, -1, 0, 0, 0, 0, -1, 1, 0],
    4: [1, 2, 4, -3, -3, 0, -2, 1, 0, 2, 2, 2, -1, 0, 2, 3, -3, 1, -1, 0, -1, -2, 2, -1],
    8: [18, 32, 64, -42, -55, -2, -28, 15, 8, 26, 33, 40, -13, -3, 26, 53, -45, 19,
        -13, -3, -8, -36, 32, -23],
}
_FROZEN_STOCHASTIC = {  # key = PRNGKey(7)
    2: [0, 0, 1, 0, -1, 0, 0, 0, 0, 0, 1, 1, 0, 0, 0, 1, -1, 0, 0, 0, 0, -1, 0, 0],
    4: [1, 2, 4, -2, -3, 0, -2, 1, 1, 1, 2, 3, -1, 0, 1, 3, -3, 1, -1, 0, -1, -2, 2, -1],
    8: [17, 32, 64, -41, -55, -2, -28, 14, 8, 26, 33, 40, -13, -3, 26, 53, -45, 19,
        -13, -2, -9, -36, 32, -22],
}


class TestFrozenPerTensorFixture:
    @pytest.mark.parametrize("bits", BITS)
    def test_nearest_codes_bit_identical(self, bits):
        with jax.threefry_partitionable(False):
            v = jax.random.normal(jax.random.PRNGKey(_FIXTURE_KEY), (24,), jnp.float32)
            codes, scale = quantize_codes(v, bits, key=None)
        assert [int(c) for c in codes] == _FROZEN_NEAREST[bits]
        assert float(scale) == _FIXTURE_SCALE

    @pytest.mark.parametrize("bits", BITS)
    def test_stochastic_codes_bit_identical(self, bits):
        with jax.threefry_partitionable(False):
            v = jax.random.normal(jax.random.PRNGKey(_FIXTURE_KEY), (24,), jnp.float32)
            codes, scale = quantize_codes(v, bits, key=jax.random.PRNGKey(7))
        assert [int(c) for c in codes] == _FROZEN_STOCHASTIC[bits]
        assert float(scale) == _FIXTURE_SCALE

    @pytest.mark.parametrize("bits", BITS)
    def test_explicit_per_tensor_matches_default(self, bits):
        with jax.threefry_partitionable(False):
            v = jax.random.normal(jax.random.PRNGKey(_FIXTURE_KEY), (24,), jnp.float32)
            codes, _ = quantize_codes(v, bits, key=None, granularity="per_tensor")
        assert [int(c) for c in codes] == _FROZEN_NEAREST[bits]


class TestGranularitySpelling:
    def test_parse_forms(self):
        assert as_granularity(None).is_per_tensor
        assert as_granularity("per_row") == Granularity("per_channel")
        assert as_granularity("per_block:64") == per_block(64)
        assert as_granularity("per_block", 32) == per_block(32)
        assert str(per_block(16)) == "per_block:16"

    def test_invalid(self):
        with pytest.raises(ValueError):
            as_granularity("per_banana")
        with pytest.raises(ValueError):
            Granularity("per_block")          # missing group_size
        with pytest.raises(ValueError):
            Granularity("per_tensor", 8)      # group_size without per_block
        with pytest.raises(ValueError):
            as_granularity("per_channel", 8)

    def test_group_packing_alignment(self):
        validate_group_packing(8, 2)
        with pytest.raises(ValueError):
            validate_group_packing(6, 2)      # 4 values/byte at 2 bits

    def test_scale_accounting(self):
        g = per_block(16)
        assert g.n_groups(100) == 7
        assert g.scale_nbytes((4, 100)) == 4 * 4 * 7
        assert as_granularity("per_tensor").scale_nbytes((4, 100)) == 4


class TestLemma4PerBlockBound:
    @given(
        bits=st.sampled_from(BITS),
        n=st.integers(8, 200),
        group=st.sampled_from([4, 8, 16, 32]),
        seed=st.integers(0, 500),
    )
    @settings(max_examples=40, deadline=None)
    def test_per_element_bound_per_block(self, bits, n, group, seed):
        """Lemma 4's per-element bound with the LOCAL scale: nearest rounding
        moves each element at most scale_blk / 2^(b-1); within every block the
        scale is that block's own max-abs, not the global one."""
        key = jax.random.PRNGKey(seed)
        # strongly non-uniform dynamic range across blocks (the k-space shape)
        v = (jax.random.normal(key, (n,), jnp.float32)
             * jnp.logspace(-3, 2, n, dtype=jnp.float32))
        q = quantize(v, bits, granularity=per_block(group))
        bound = expand_block_scale(q.scale, group, n) / 2 ** (bits - 1)
        err = jnp.abs(q.dequantize() - v)
        assert float(jnp.max(err - bound)) <= 1e-5 * float(jnp.max(bound))

    @given(bits=st.sampled_from(BITS), seed=st.integers(0, 500))
    @settings(max_examples=20, deadline=None)
    def test_per_channel_bound(self, bits, seed):
        key = jax.random.PRNGKey(seed)
        v = jax.random.normal(key, (6, 64), jnp.float32) * jnp.logspace(
            -2, 2, 6, dtype=jnp.float32)[:, None]
        q = quantize(v, bits, granularity="per_channel")
        assert q.scale.shape == (6, 1)
        err = jnp.abs(q.dequantize() - v)
        bound = q.scale / 2 ** (bits - 1)
        assert float(jnp.max(err - bound)) <= 1e-6

    def test_blockwise_preserves_small_coefficients(self):
        # block-structured dynamic range (each 32-group has its own magnitude,
        # like k-space bands). The single per-tensor scale sets its rounding
        # step from the dominant block, flushing small-magnitude blocks to
        # zero (100% error there); local scales keep them representable.
        mags = jnp.repeat(jnp.logspace(-3, 2, 16, dtype=jnp.float32), 32)
        v = jax.random.normal(jax.random.PRNGKey(0), (512,), jnp.float32) * mags
        small = mags < 1e-1 * float(jnp.max(jnp.abs(v)))
        vs = v[small]
        e_tensor = float(jnp.linalg.norm(fake_quantize(v, 4)[small] - vs))
        e_block = float(jnp.linalg.norm(
            fake_quantize(v, 4, granularity=per_block(32))[small] - vs))
        assert e_tensor > 0.6 * float(jnp.linalg.norm(vs))   # mostly flushed
        assert e_block < 0.25 * float(jnp.linalg.norm(vs))   # locally resolved

    def test_ragged_last_block(self):
        v = jnp.arange(1.0, 11.0)      # n=10, g=4 -> blocks 4,4,2
        q = quantize(v, 8, granularity=per_block(4))
        assert q.scale.shape == (3,)
        np.testing.assert_allclose(np.asarray(q.scale), [4.0, 8.0, 10.0])
        np.testing.assert_allclose(np.asarray(q.dequantize()), np.asarray(v),
                                   rtol=0.02)


@pytest.mark.slow
class TestGroupScaledQmm:
    @pytest.mark.parametrize("bits", BITS)
    @pytest.mark.parametrize("group", [8, 32])
    def test_kernel_matches_ref_real(self, bits, group):
        key = jax.random.PRNGKey(1)
        m, k, n = 8, 200, 48
        x = jax.random.normal(key, (m, k), jnp.float32)
        w = (jax.random.normal(jax.random.fold_in(key, 1), (n, k), jnp.float32)
             * jnp.logspace(-2, 2, k, dtype=jnp.float32))
        pw = pack_weights(w, bits, jax.random.fold_in(key, 2),
                          granularity=per_block(group))
        assert pw.scale.shape == (n, (k + group - 1) // group)
        ref = qmm_group_ref(x, pw.packed, pw.scale, bits, k, group)
        out = qmm(x, pw, use_pallas=True, interpret=True)
        rel = float(jnp.linalg.norm(out - ref) / (jnp.linalg.norm(ref) + 1e-30))
        assert rel <= 1e-5

    @pytest.mark.parametrize("bits", BITS)
    def test_semantics_match_fake_quantize(self, bits):
        """qmm(per_block) == x @ Q_blockwise(w)^T — the framework quantizer."""
        key = jax.random.PRNGKey(2)
        x = jax.random.normal(key, (4, 96), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (16, 96), jnp.float32)
        kq = jax.random.fold_in(key, 2)
        pw = pack_weights(w, bits, kq, granularity=per_block(16))
        out = qmm(x, pw, use_pallas=False)
        w_deq = fake_quantize(w, bits, kq, granularity=per_block(16))
        np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w_deq.T),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("bits", BITS)
    def test_complex_operator_matvec_rmatvec(self, bits):
        key = jax.random.PRNGKey(3)
        phi = (jax.random.normal(key, (24, 48))
               + 1j * jax.random.normal(jax.random.fold_in(key, 1), (24, 48))
               ).astype(jnp.complex64)
        op = PackedStreamingOperator.pack(phi, bits, jax.random.fold_in(key, 2),
                                          granularity=per_block(8))
        x = jax.random.normal(jax.random.fold_in(key, 3), (48,), jnp.float32)
        r = (jax.random.normal(jax.random.fold_in(key, 4), (24,))
             + 1j * jax.random.normal(jax.random.fold_in(key, 5), (24,))
             ).astype(jnp.complex64)
        # kernel (interpret) vs pure-jnp ref, both orientations
        a = PackedStreamingOperator(op.packed, use_pallas=True, interpret=True)
        b = PackedStreamingOperator(op.packed, use_pallas=False)
        np.testing.assert_allclose(np.asarray(a.mv(x)), np.asarray(b.mv(x)),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(a.rmv(r)), np.asarray(b.rmv(r)),
                                   rtol=1e-5, atol=1e-5)

    def test_group_scale_bytes_accounting(self):
        phi = jax.random.normal(jax.random.PRNGKey(4), (64, 128), jnp.float32)
        op = PackedStreamingOperator.pack(phi, 4, granularity=per_block(32))
        # fwd (64,128): 64*4 groups; codes bytes unchanged vs per-tensor
        assert op.scale_nbytes == 64 * (128 // 32) * 4
        op_pt = PackedStreamingOperator.pack(phi, 4)
        assert op.nbytes == op_pt.nbytes


class TestPackOperatorSharedConflict:
    """Satellite: ONE clear error for shared=True with per-orientation scales."""

    def test_shared_with_per_channel_bool(self):
        phi = jax.random.normal(jax.random.PRNGKey(5), (16, 24), jnp.float32)
        with pytest.raises(ValueError, match="shared=False.*per_tensor"):
            pack_operator(phi, 4, shared=True, per_channel=True)

    def test_shared_with_group_granularity(self):
        phi = jax.random.normal(jax.random.PRNGKey(5), (16, 24), jnp.float32)
        with pytest.raises(ValueError, match="shared=False.*per_tensor"):
            pack_operator(phi, 4, shared=True, granularity=per_block(8))

    def test_shared_per_tensor_still_fine(self):
        phi = jax.random.normal(jax.random.PRNGKey(5), (16, 24), jnp.float32)
        op = pack_operator(phi, 4, shared=True, granularity="per_tensor")
        assert op.fwd_re.granularity.is_per_tensor


class TestQnihtGranularity:
    @pytest.mark.slow
    def test_per_tensor_bit_identical_to_default(self):
        key = jax.random.PRNGKey(10)
        prob = make_gaussian_problem(64, 128, 6, snr_db=25.0, key=key)
        kw = dict(bits_phi=4, bits_y=8, key=key, requantize="fixed",
                  backend="packed")
        r_def = qniht(prob.phi, prob.y, prob.s, 20, **kw)
        r_pt = qniht(prob.phi, prob.y, prob.s, 20,
                     scale_granularity="per_tensor", **kw)
        assert float(jnp.max(jnp.abs(r_def.x - r_pt.x))) == 0.0

    @pytest.mark.slow
    def test_group_scaled_runs_and_recovers(self):
        key = jax.random.PRNGKey(11)
        prob = make_gaussian_problem(64, 128, 6, snr_db=25.0, key=key)
        kw = dict(bits_phi=4, bits_y=8, key=key, requantize="fixed",
                  backend="packed")
        r_pt = qniht(prob.phi, prob.y, prob.s, 30, **kw)
        r_gb = qniht(prob.phi, prob.y, prob.s, 30,
                     scale_granularity="per_block", group_size=16, **kw)
        e_pt = float(relative_error(r_pt.x, prob.x_true))
        e_gb = float(relative_error(r_gb.x, prob.x_true))
        assert np.isfinite(e_gb)
        assert e_gb <= e_pt + 0.05   # finer scales should not hurt recovery

    def test_granularity_requires_packed_backend(self):
        key = jax.random.PRNGKey(12)
        prob = make_gaussian_problem(32, 64, 3, key=key)
        with pytest.raises(ValueError, match="packed"):
            qniht(prob.phi, prob.y, 3, 5, bits_phi=4, bits_y=8, key=key,
                  scale_granularity="per_block", group_size=16)


class TestPerBandKspace:
    def test_band_geometry(self):
        prob = make_mri_problem(32, 40, 0.4, jax.random.PRNGKey(0))
        bands = kspace_radial_bands(prob.op, n_bands=8)
        assert bands.shape == (prob.op.shape[0],)
        assert int(bands.min()) >= 0 and int(bands.max()) <= 7
        # DC (flat index 0 in the unshifted convention) sits in band 0
        dc_pos = int(jnp.argmax(prob.op.indices == 0))
        assert prob.op.indices[dc_pos] == 0
        assert int(bands[dc_pos]) == 0

    def test_band_scales_bound_samples(self):
        prob = make_mri_problem(32, 40, 0.4, jax.random.PRNGKey(1))
        bands = kspace_radial_bands(prob.op, n_bands=8)
        scales = kspace_band_scales(prob.y, bands, 8)
        mag = jnp.maximum(jnp.abs(prob.y.real), jnp.abs(prob.y.imag))
        assert float(jnp.max(mag - scales[bands])) <= 1e-6

    @pytest.mark.parametrize("bits", [4, 2])
    def test_per_band_quantization_noise_much_smaller(self, bits):
        """The whole point: per-band ŷ is far closer to y than per-tensor ŷ
        because the shared c_y step is set by the huge DC coefficients."""
        prob = make_mri_problem(64, 120, 0.35, jax.random.PRNGKey(2))
        key = jax.random.PRNGKey(3)
        y_pt = quantize_observations(prob.y, bits, key)
        y_pb = quantize_observations(prob.y, bits, key, granularity="per_band",
                                     op=prob.op, n_bands=16)
        e_pt = float(jnp.linalg.norm(y_pt - prob.y))
        e_pb = float(jnp.linalg.norm(y_pb - prob.y))
        assert e_pb < 0.5 * e_pt

    def test_per_band_error_bound_per_sample(self):
        prob = make_mri_problem(32, 40, 0.4, jax.random.PRNGKey(4))
        bits, nb = 4, 8
        yq = quantize_observations(prob.y, bits, jax.random.PRNGKey(5),
                                   granularity="per_band", op=prob.op, n_bands=nb)
        bands = kspace_radial_bands(prob.op, n_bands=nb)
        step = kspace_band_scales(prob.y, bands, nb)[bands] / 2 ** (bits - 2)
        # stochastic rounding moves each component at most one full step
        assert float(jnp.max(jnp.abs(yq.real - prob.y.real) - step)) <= 1e-6
        assert float(jnp.max(jnp.abs(yq.imag - prob.y.imag) - step)) <= 1e-6

    def test_batched_rows_match_singles(self):
        prob = make_mri_problem(32, 40, 0.4, jax.random.PRNGKey(6))
        key = jax.random.PRNGKey(7)
        Y = jnp.stack([prob.y, 3.0 * prob.y])
        Yq = quantize_observations(Y, 4, key, granularity="per_band",
                                   op=prob.op, n_bands=8)
        for b, row in enumerate([prob.y, 3.0 * prob.y]):
            single = quantize_observations(row, 4, key, granularity="per_band",
                                           op=prob.op, n_bands=8)
            np.testing.assert_allclose(np.asarray(Yq[b]), np.asarray(single),
                                       rtol=1e-6, atol=1e-6)

    def test_per_tensor_matches_fake_quantize(self):
        prob = make_mri_problem(32, 40, 0.4, jax.random.PRNGKey(8))
        key = jax.random.PRNGKey(9)
        a = quantize_observations(prob.y, 8, key)
        b = fake_quantize(prob.y, 8, key)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_rejects_unknown_granularity_and_missing_op(self):
        prob = make_mri_problem(32, 40, 0.4, jax.random.PRNGKey(10))
        with pytest.raises(ValueError, match="per_band"):
            quantize_observations(prob.y, 4, jax.random.PRNGKey(0),
                                  granularity="per_pixel")
        with pytest.raises(ValueError, match="op"):
            quantize_observations(prob.y, 4, jax.random.PRNGKey(0),
                                  granularity="per_band")
