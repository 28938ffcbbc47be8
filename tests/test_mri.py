"""Matrix-free MRI workload: SubsampledFourierOperator + sensing/mri contracts.

Covers:
* adjointness ⟨Φx, r⟩ == ⟨x, Φ†r⟩ for real and complex inputs (F unitary ⇒ the
  zero-fill/IFFT adjoint is exact, not approximate),
* the zero-fill gather through the inverse map equals a scatter bit for bit,
  and the map survives pytree flattening and jit,
* parity of the matrix-free operator vs an explicitly materialized partial-DFT
  Φ on small grids (mv, rmv, and full qniht iterates),
* phantom/mask/observation substrate properties,
* the ISSUE-2 acceptance run: 128×128 (N = 16384) recovery at b_y = 8 reaching
  PSNR ≥ 30 dB without a dense Φ,
* operator-input validation (bits_phi/backend rejected, 2-D y rejected).
"""
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.mri_brain import CONFIG as BRAIN
from repro.core import (
    DenseOperator,
    SubsampledFourierOperator,
    niht,
    psnr,
    qniht,
    qniht_batch,
    relative_error,
)
from repro.sensing import (
    brain_phantom,
    cartesian_mask,
    make_mri_problem,
    mri_observations,
    quantize_observations,
    shepp_logan,
    sparsify_image,
)


def _small_op(r=16, frac=0.4, seed=0):
    mask = cartesian_mask(r, frac, jax.random.PRNGKey(seed))
    return SubsampledFourierOperator.from_mask(mask), mask


def _one_point_mask(r, flat):
    mask = np.zeros((r, r), bool)
    mask.flat[flat] = True
    return mask


# k-space masks for the zero-fill check: the extremes, a small random one and
# the 256² 35 % variable-density mask of configs/mri_brain
_ZERO_FILL_MASKS = {
    "single": lambda: _one_point_mask(8, 37),
    "dc_only": lambda: _one_point_mask(8, 0),
    "every_point": lambda: np.ones((8, 8), bool),
    "random16": lambda: np.random.default_rng(4).random((16, 16)) < 0.4,
    "brain256": lambda: cartesian_mask(
        BRAIN.resolution, BRAIN.fraction, jax.random.split(jax.random.PRNGKey(BRAIN.seed), 3)[1],
        BRAIN.density, BRAIN.center_fraction),
}


_jit_rmv = jax.jit(lambda op, v: op.rmv(v))
_jit_identity = jax.jit(lambda op: op)


@partial(jax.jit, static_argnames="r")
def _scatter_rmv(idx, v, *, r):
    """P_Ωᵀv written as the zero-fill scatter, then the orthonormal ifft2."""
    full = jnp.zeros((*v.shape[:-1], r * r), v.dtype).at[..., idx].set(v)
    img = jnp.fft.ifft2(full.reshape(*v.shape[:-1], r, r), norm="ortho")
    return img.reshape(*v.shape[:-1], r * r)


def _materialize(op):
    """Explicit (M, N) partial-DFT matrix: Φ e_j for every basis vector."""
    n = op.shape[1]
    eye = jnp.eye(n, dtype=jnp.float32)
    return op.mv(eye).T  # column j = Φ e_j


class TestSubsampledFourierOperator:
    def test_adjoint_identity_real_input(self):
        op, _ = _small_op()
        key = jax.random.PRNGKey(1)
        x = jax.random.normal(key, (op.shape[1],), jnp.float32)
        r = (jax.random.normal(jax.random.fold_in(key, 1), (op.shape[0],))
             + 1j * jax.random.normal(jax.random.fold_in(key, 2), (op.shape[0],))
             ).astype(jnp.complex64)
        lhs = jnp.vdot(op.mv(x), r)
        rhs = jnp.vdot(x.astype(jnp.complex64), op.rmv(r))
        assert float(jnp.abs(lhs - rhs)) / float(jnp.abs(lhs)) < 1e-5

    def test_adjoint_identity_complex_input(self):
        op, _ = _small_op(r=12, frac=0.5, seed=3)
        key = jax.random.PRNGKey(2)
        x = (jax.random.normal(key, (op.shape[1],))
             + 1j * jax.random.normal(jax.random.fold_in(key, 1), (op.shape[1],))
             ).astype(jnp.complex64)
        r = (jax.random.normal(jax.random.fold_in(key, 2), (op.shape[0],))
             + 1j * jax.random.normal(jax.random.fold_in(key, 3), (op.shape[0],))
             ).astype(jnp.complex64)
        lhs = jnp.vdot(op.mv(x), r)
        rhs = jnp.vdot(x, op.rmv(r))
        assert float(jnp.abs(lhs - rhs)) / float(jnp.abs(lhs)) < 1e-5

    def test_parity_with_materialized_phi(self):
        op, _ = _small_op(r=8, frac=0.6, seed=4)
        phi = _materialize(op)
        key = jax.random.PRNGKey(3)
        x = jax.random.normal(key, (op.shape[1],), jnp.float32)
        v = (jax.random.normal(jax.random.fold_in(key, 1), (op.shape[0],))
             + 1j * jax.random.normal(jax.random.fold_in(key, 2), (op.shape[0],))
             ).astype(jnp.complex64)
        np.testing.assert_allclose(np.asarray(op.mv(x)), np.asarray(phi @ x),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(op.rmv(v)),
                                   np.asarray(jnp.conj(phi.T) @ v),
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.slow
    def test_qniht_parity_matrix_free_vs_dense(self):
        """The solver produces the same iterates whether Φ is implicit or an
        explicitly materialized dense array (full-precision path)."""
        op, _ = _small_op(r=12, frac=0.6, seed=5)
        phi = _materialize(op)
        key = jax.random.PRNGKey(4)
        n = op.shape[1]
        x = jnp.zeros((n,)).at[jax.random.choice(key, n, (6,), replace=False)].set(
            # jaxlint: allow=JL002 -- fixture: support/amplitude correlation is harmless
            jax.random.uniform(key, (6,), minval=0.5, maxval=1.0))
        y = op.mv(x)
        kw = dict(real_signal=True, nonneg=True)
        r_free = qniht(op, y, 6, 25, **kw)
        r_dense = qniht(phi, y, 6, 25, **kw)
        ref = float(jnp.linalg.norm(r_dense.x)) + 1e-12
        assert float(jnp.linalg.norm(r_free.x - r_dense.x)) <= 1e-4 * ref
        np.testing.assert_allclose(np.asarray(r_free.trace.resid_q),
                                   np.asarray(r_dense.trace.resid_q),
                                   rtol=1e-3, atol=1e-5)

    def test_batched_mv_matches_singles(self):
        op, _ = _small_op()
        X = jax.random.normal(jax.random.PRNGKey(5), (4, op.shape[1]), jnp.float32)
        batched = op.mv(X)
        assert batched.shape == (4, op.shape[0])
        for b in range(4):
            np.testing.assert_allclose(np.asarray(batched[b]),
                                       np.asarray(op.mv(X[b])), rtol=1e-5, atol=1e-6)

    def test_nbytes_counts_pattern_only(self):
        op, mask = _small_op(r=16)
        m = int(np.asarray(mask).sum())
        # int32 indices + 1-bit mask + the int32 inverse map of the zero-fill
        assert op.nbytes == m * 4 + (16 * 16 + 7) // 8 + 16 * 16 * 4
        # the point: orders of magnitude below the dense complex64 Φ
        assert op.nbytes < m * 16 * 16 * 8 / 100

    def test_from_mask_rejects_bad_masks(self):
        with pytest.raises(ValueError):
            SubsampledFourierOperator.from_mask(np.zeros((8, 8), bool))
        with pytest.raises(ValueError):
            SubsampledFourierOperator.from_mask(np.ones((8, 4), bool))

    def test_mask_round_trip(self):
        op, mask = _small_op(r=16, seed=7)
        np.testing.assert_array_equal(np.asarray(op.mask()), np.asarray(mask))

    @pytest.mark.parametrize("batch", [None, 8], ids=["unbatched", "B8"])
    @pytest.mark.parametrize("mask_name", sorted(_ZERO_FILL_MASKS))
    def test_rmv_equals_scatter_zero_fill_bit_for_bit(self, mask_name, batch):
        """The gather through the inverse map is the zero-fill scatter P_Ωᵀv,
        bit for bit, followed by the same ifft2."""
        mask = _ZERO_FILL_MASKS[mask_name]()
        op = SubsampledFourierOperator.from_mask(mask)
        r, m = op.resolution, op.shape[0]
        lead = () if batch is None else (batch,)
        key = jax.random.PRNGKey(6)
        v = jax.lax.complex(jax.random.normal(key, (*lead, m)),
                            jax.random.normal(jax.random.fold_in(key, 1), (*lead, m)))
        got = np.asarray(_jit_rmv(op, v))
        want = np.asarray(_scatter_rmv(op.indices, v, r=r))
        assert got.shape == (*lead, r * r) and got.dtype == np.complex64
        np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))

    def test_pytree_and_jit_keep_the_inverse_map(self):
        op, mask = _small_op(r=16, seed=9)
        m = op.shape[0]
        inv = np.asarray(op.inverse)
        idx = np.asarray(op.indices)
        # the map: each sampled position points at its place, the rest at slot M
        np.testing.assert_array_equal(inv[idx], np.arange(m))
        assert inv.dtype == np.int32 and inv.shape == (16 * 16,)
        assert np.all(inv[~np.asarray(mask).ravel()] == m)
        # indices alone build the same map on the host
        np.testing.assert_array_equal(
            np.asarray(SubsampledFourierOperator(op.indices, 16).inverse), inv)

        leaves, treedef = jax.tree_util.tree_flatten(op)
        assert len(leaves) == 2
        back = jax.tree_util.tree_unflatten(treedef, leaves)
        assert back.resolution == 16
        np.testing.assert_array_equal(np.asarray(back.inverse), inv)

        through = _jit_identity(op)
        np.testing.assert_array_equal(np.asarray(through.inverse), inv)
        np.testing.assert_array_equal(np.asarray(through.indices), idx)
        re = jax.random.normal(jax.random.PRNGKey(8), (3, m))
        v = jax.lax.complex(re, -re)
        np.testing.assert_array_equal(np.asarray(_jit_rmv(op, v)).view(np.uint32),
                                      np.asarray(op.rmv(v)).view(np.uint32))


class TestMRISubstrate:
    def test_shepp_logan_range_and_structure(self):
        img = np.asarray(shepp_logan(64))
        assert img.shape == (64, 64)
        assert img.min() >= 0.0 and img.max() == pytest.approx(1.0)
        assert (img == 0).any()  # background stays empty

    def test_brain_phantom_deterministic_in_key(self):
        a = brain_phantom(48, jax.random.PRNGKey(0))
        b = brain_phantom(48, jax.random.PRNGKey(0))
        c = brain_phantom(48, jax.random.PRNGKey(1))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.array_equal(np.asarray(a), np.asarray(c))
        # piecewise constant: few distinct intensity levels
        assert len(np.unique(np.round(np.asarray(a), 5))) < 40

    def test_sparsify_keeps_top_s(self):
        img = shepp_logan(32)
        s = 50
        x = sparsify_image(img, s)
        assert int(jnp.sum(jnp.abs(x) > 0)) == s
        kept = np.sort(np.abs(np.asarray(x)[np.abs(np.asarray(x)) > 0]))
        dropped = np.sort(np.abs(np.asarray(img.ravel() - x)))
        assert kept.min() >= dropped.max() - 1e-6

    @pytest.mark.parametrize("density", ["uniform", "variable"])
    def test_cartesian_mask_fraction_and_center(self, density):
        r, frac = 32, 0.3
        mask = cartesian_mask(r, frac, jax.random.PRNGKey(0), density=density)
        assert mask.shape == (r, r) and mask.dtype == bool
        assert abs(int(mask.sum()) - int(round(frac * r * r))) <= 1
        assert mask[0, 0]  # DC always sampled (center block, unshifted convention)

    def test_variable_density_concentrates_low_freq(self):
        r = 64
        key = jax.random.PRNGKey(1)
        mu = np.fft.fftshift(cartesian_mask(r, 0.3, key, density="uniform"))
        mv = np.fft.fftshift(cartesian_mask(r, 0.3, key, density="variable"))
        lin = np.arange(r) - r // 2
        xx, yy = np.meshgrid(lin, lin, indexing="ij")
        d = np.sqrt(xx**2 + yy**2)
        assert d[mv].mean() < d[mu].mean() - 1.0

    def test_mask_validation(self):
        with pytest.raises(ValueError):
            cartesian_mask(16, 0.0, jax.random.PRNGKey(0))
        with pytest.raises(ValueError):
            cartesian_mask(16, 0.3, jax.random.PRNGKey(0), density="radial")
        with pytest.raises(ValueError, match="center block"):
            # center block alone would exceed the requested 2% budget
            cartesian_mask(256, 0.02, jax.random.PRNGKey(0), center_fraction=0.04)

    def test_batched_observations_noise_per_row(self):
        op, _ = _small_op(r=32, frac=0.5)
        X = jnp.stack([sparsify_image(shepp_logan(32), 60),
                       sparsify_image(brain_phantom(32, jax.random.PRNGKey(0)), 60)])
        Y, E = mri_observations(op, X, 20.0, jax.random.PRNGKey(1))
        assert Y.shape == (2, op.shape[0]) and E.shape == Y.shape
        for b in range(2):
            snr = 10 * np.log10(
                float(jnp.real(jnp.vdot(Y[b] - E[b], Y[b] - E[b])))
                / float(jnp.real(jnp.vdot(E[b], E[b]))))
            assert abs(snr - 20.0) < 2.5

    def test_observation_noise_calibration(self):
        op, _ = _small_op(r=32, frac=0.5)
        x = sparsify_image(shepp_logan(32), 60)
        y, e = mri_observations(op, x, 20.0, jax.random.PRNGKey(2))
        snr = 10 * np.log10(float(jnp.real(jnp.vdot(y - e, y - e)))
                            / float(jnp.real(jnp.vdot(e, e))))
        assert abs(snr - 20.0) < 2.0
        y0, e0 = mri_observations(op, x, None, jax.random.PRNGKey(2))
        assert float(jnp.max(jnp.abs(e0))) == 0.0

    def test_quantize_observations_unbiased_scale(self):
        op, _ = _small_op(r=16, frac=0.5)
        x = sparsify_image(shepp_logan(16), 30)
        y, _ = mri_observations(op, x, None, jax.random.PRNGKey(3))
        yq = quantize_observations(y, 8, jax.random.PRNGKey(4))
        assert yq.dtype == y.dtype
        rel = float(jnp.linalg.norm(yq - y) / jnp.linalg.norm(y))
        assert 0.0 < rel < 0.05


class TestEndToEndMRI:
    @pytest.mark.slow
    def test_acceptance_128_psnr30_at_8bit(self):
        """ISSUE 2 acceptance: 128×128 (N = 16384) matrix-free recovery at
        b_y = 8 reaches PSNR ≥ 30 dB — a size whose dense Φ (~750 MB) the
        old array-only qniht could not represent sensibly."""
        r, s = 128, 500
        key = jax.random.PRNGKey(5)
        prob = make_mri_problem(r, s, 0.35, key)
        res = qniht(prob.op, prob.y, s, 40, bits_y=8, key=key,
                    real_signal=True, nonneg=True)
        ps = float(psnr(res.x.reshape(r, r), prob.x_true.reshape(r, r)))
        assert ps >= 30.0
        assert float(relative_error(res.x, prob.x_true)) < 0.15

    @pytest.mark.slow
    def test_batch_matches_single(self):
        r, s = 32, 40
        key = jax.random.PRNGKey(6)
        prob = make_mri_problem(r, s, 0.45, key)
        Y = jnp.stack([prob.y, 0.5 * prob.y])
        kw = dict(bits_y=8, key=key, real_signal=True, nonneg=True)
        res_b = qniht_batch(prob.op, Y, s, 20, **kw)
        res_s = qniht(prob.op, prob.y, s, 20, **kw)
        ref = float(jnp.linalg.norm(res_s.x)) + 1e-12
        assert float(jnp.linalg.norm(res_b.x[0] - res_s.x)) <= 1e-4 * ref

    def test_operator_input_validation(self):
        prob = make_mri_problem(16, 10, 0.5, jax.random.PRNGKey(7))
        key = jax.random.PRNGKey(0)
        with pytest.raises(ValueError):  # operators own their representation
            qniht(prob.op, prob.y, 10, 5, bits_phi=8, key=key)
        with pytest.raises(ValueError):  # nothing dense to pack
            qniht(prob.op, prob.y, 10, 5, backend="packed", bits_phi=8,
                  key=key, requantize="fixed")

    def test_qniht_rejects_2d_y(self):
        prob = make_mri_problem(16, 10, 0.5, jax.random.PRNGKey(8))
        with pytest.raises(ValueError, match="qniht_batch"):
            qniht(prob.op, jnp.stack([prob.y, prob.y]), 10, 5)

    def test_dense_operator_input_matches_array_input(self):
        """as_operator seam: passing DenseOperator(phi) is the same
        computation as passing phi itself."""
        key = jax.random.PRNGKey(9)
        phi = jax.random.normal(key, (32, 64), jnp.float32)
        x = jnp.zeros((64,)).at[:3].set(jnp.asarray([1.0, -0.7, 0.4]))
        y = phi @ x
        r_arr = niht(phi, y, 3, 15)
        r_op = qniht(DenseOperator(phi), y, 3, 15)
        np.testing.assert_allclose(np.asarray(r_op.x), np.asarray(r_arr.x),
                                   rtol=1e-6, atol=1e-7)


class TestCartesianMaskEdgeCases:
    """ISSUE-4: mask writes pinned down — .flat guarantees write-through where
    ravel() only happens to (contiguity-dependent) — plus budget edge cases."""

    def test_fraction_one_fills_grid(self):
        mask = cartesian_mask(16, 1.0, jax.random.PRNGKey(0))
        assert mask.all()

    def test_center_block_consumes_whole_budget(self):
        # r=8, center_fraction=0.04 → half_c = max(1, ...) = 1 → 2×2 center
        # block = 4 samples = the entire requested budget: no random picks.
        r, frac = 8, 4 / 64
        mask = cartesian_mask(r, frac, jax.random.PRNGKey(1))
        assert int(mask.sum()) == 4
        centered = np.fft.fftshift(mask)
        c = r // 2
        assert centered[c - 1:c + 1, c - 1:c + 1].all()

    def test_tiny_resolution(self):
        mask = cartesian_mask(4, 0.5, jax.random.PRNGKey(2))
        assert mask.shape == (4, 4) and int(mask.sum()) == 8

    def test_random_picks_actually_land(self):
        """Every requested random sample must materialize in the mask."""
        for seed in range(3):
            mask = cartesian_mask(32, 0.3, jax.random.PRNGKey(seed))
            assert int(mask.sum()) == round(0.3 * 32 * 32)


class TestWaveletBasisProblem:
    def test_problem_fields_and_shapes(self):
        prob = make_mri_problem(32, 80, 0.5, jax.random.PRNGKey(10),
                                sparsity_basis="haar")
        assert prob.sparsity_basis == "haar"
        assert prob.op.shape == (prob.op.kspace_op.shape[0], 32 * 32)
        assert prob.x_true.shape == (32 * 32,)
        assert prob.image_true.shape == (32 * 32,)
        # truth is the FULL phantom, not a thresholded one
        img = shepp_logan(32).ravel()
        np.testing.assert_allclose(np.asarray(prob.image_true), np.asarray(img),
                                   rtol=1e-6, atol=1e-6)
        # x_true is its wavelet transform; to_image inverts it exactly
        np.testing.assert_allclose(np.asarray(prob.to_image(prob.x_true)),
                                   np.asarray(img), rtol=1e-4, atol=1e-5)

    def test_pixel_problem_unchanged_defaults(self):
        prob = make_mri_problem(32, 80, 0.5, jax.random.PRNGKey(11))
        assert prob.sparsity_basis == "pixel"
        assert prob.synthesis is None
        np.testing.assert_array_equal(np.asarray(prob.image_true),
                                      np.asarray(prob.x_true))
        np.testing.assert_array_equal(np.asarray(prob.to_image(prob.x_true)),
                                      np.asarray(prob.x_true))

    def test_observations_consistent_with_composed_operator(self):
        """y sampled from the image's k-space == op.mv(x_true) up to the
        (orthonormal) W†W round trip."""
        prob = make_mri_problem(32, 80, 0.5, jax.random.PRNGKey(12),
                                sparsity_basis="db4")
        via_op = prob.op.mv(prob.x_true)
        assert float(jnp.linalg.norm(via_op - prob.y)) <= \
            1e-4 * float(jnp.linalg.norm(prob.y))

    def test_quantize_observations_per_band_on_composition(self):
        prob = make_mri_problem(32, 80, 0.5, jax.random.PRNGKey(13),
                                sparsity_basis="haar")
        yq = quantize_observations(prob.y, 8, jax.random.PRNGKey(14),
                                   granularity="per_band", op=prob.op, n_bands=8)
        assert yq.shape == prob.y.shape and yq.dtype == prob.y.dtype
        rel = float(jnp.linalg.norm(yq - prob.y) / jnp.linalg.norm(prob.y))
        assert 0.0 < rel < 0.05

    def test_invalid_basis_rejected(self):
        with pytest.raises(ValueError, match="sparsity_basis"):
            make_mri_problem(32, 80, 0.5, jax.random.PRNGKey(15),
                             sparsity_basis="dct")

    @pytest.mark.slow
    def test_acceptance_full_image_128_psnr30(self):
        """ISSUE-4 acceptance: the FULL (non-sparsified) 128×128 phantom at
        35% variable-density sampling recovers through Φ = P_Ω F W† (matrix-
        free throughout) at ≥ 30 dB — for f32 observations AND the bits_y=8
        per-band quantized path."""
        r, s = 128, 2000
        key = jax.random.PRNGKey(16)
        prob = make_mri_problem(r, s, 0.35, key, sparsity_basis="haar")
        img_true = prob.image_true.reshape(r, r)

        res = qniht(prob.op, prob.y, s, 40, real_signal=True)
        ps_f32 = float(psnr(prob.to_image(res.x).reshape(r, r), img_true))
        assert ps_f32 >= 30.0

        yq = quantize_observations(prob.y, 8, key, granularity="per_band",
                                   op=prob.op, n_bands=16)
        res_q = qniht(prob.op, yq, s, 40, real_signal=True)
        ps_q = float(psnr(prob.to_image(res_q.x).reshape(r, r), img_true))
        assert ps_q >= 30.0

    @pytest.mark.slow
    def test_wavelet_recovery_beats_pixel_on_full_image(self):
        """The point of the tentpole, at smoke size: recovering the full
        phantom through W† beats pretending it is pixel-sparse."""
        r, s = 64, 500
        key = jax.random.PRNGKey(17)
        prob = make_mri_problem(r, s, 0.35, key, sparsity_basis="haar")
        img_true = prob.image_true.reshape(r, r)
        res_w = qniht(prob.op, prob.y, s, 25, real_signal=True)
        ps_w = float(psnr(prob.to_image(res_w.x).reshape(r, r), img_true))
        res_p = qniht(prob.op.kspace_op, prob.y, s, 25,
                      real_signal=True, nonneg=True)
        ps_p = float(psnr(jnp.real(res_p.x).reshape(r, r), img_true))
        assert ps_w >= ps_p + 3.0
