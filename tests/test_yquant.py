"""The per-band observation quantizer as one device program.

``quantize_observations(granularity="per_band")`` quantizes each acquired
k-space sample with the scale of its radial band. It is one compiled program
per (shape, bits, n_bands), band map included, with no scatter and no sort,
under the named scope ``yquant``. Its results
are pinned bit for bit against a plain numpy quantizer written here (numpy
band map and per-band max, the same ``jax.random`` draws), at the SMOKE size
and at the 256² sampling mask of ``configs/mri_brain.py``. With one band it
is the paper's single scale c_y per acquisition: the per-tensor quantizer of
each row.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.mri_brain import CONFIG, WAVELET_SMOKE
from repro.core.operators import (
    ComposedOperator,
    SubsampledFourierOperator,
    WaveletSynthesisOperator,
)
from repro.sensing import (
    brain_phantom,
    cartesian_mask,
    kspace_radial_bands,
    quantize_observations,
)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
N_BANDS = 16


def _operator(cfg):
    """Φ = P_Ω F W† on the configuration's mask, drawn as make_mri_problem
    draws it (the mask key is the second of three splits of the seed)."""
    kmask = jax.random.split(jax.random.PRNGKey(cfg.seed), 3)[1]
    mask = cartesian_mask(cfg.resolution, cfg.fraction, kmask, cfg.density,
                          cfg.center_fraction)
    return ComposedOperator(SubsampledFourierOperator.from_mask(mask),
                            WaveletSynthesisOperator(cfg.resolution, "haar"))


_OPS = {}


def _setup(cfg, rows=8):
    """The operator and ``rows`` brain slices' k-space samples (rows, M)."""
    if cfg.name not in _OPS:
        op = _operator(cfg)
        imgs = jnp.stack([brain_phantom(cfg.resolution, jax.random.PRNGKey(40 + b)).ravel()
                          for b in range(rows)])
        _OPS[cfg.name] = (op, op.kspace_op.mv(imgs))
    return _OPS[cfg.name]


def plain_bands(indices, r, n_bands):
    """Radial band of each flat index, in numpy float32."""
    idx = np.asarray(indices, np.int64)
    fr = ((idx // r + r // 2) % r) - r // 2
    fc = ((idx % r + r // 2) % r) - r // 2
    dist = np.sqrt(fr.astype(np.float32) ** 2 + fc.astype(np.float32) ** 2)
    d_max = np.sqrt(np.float32(2.0)) * np.float32(r / 2.0)
    band = np.floor(dist / d_max * np.float32(n_bands)).astype(np.int32)
    return np.clip(band, 0, n_bands - 1)


def plain_per_band(Y, bits, key, band, n_bands):
    """b-bit stochastic quantization of each row of ``Y`` (B, M), one scale
    per band (its largest |re|, |im|; 1 where that is 0 or the band is
    empty); every row takes the same draws of ``key``."""
    Y = np.asarray(Y)
    mag = np.maximum(np.abs(Y.real), np.abs(Y.imag))
    scale = np.ones((Y.shape[0], n_bands), np.float32)
    for b in range(n_bands):
        if (band == b).any():
            top = mag[:, band == b].max(axis=1)
            scale[:, b] = np.where(top > 0, top, np.float32(1.0))
    s = scale[:, band]
    k = np.float32(2 ** (bits - 2))
    kre, kim = jax.random.split(key)

    def q(v, kk):
        scaled = np.clip(v / s, np.float32(-1.0), np.float32(1.0)) * k
        low = np.floor(scaled)
        u = np.asarray(jax.random.uniform(kk, (Y.shape[1],), jnp.float32))
        codes = np.clip(low + (u < scaled - low).astype(np.float32), -k, k)
        return codes.astype(np.int8).astype(np.float32) * (s / k)

    out = np.empty(Y.shape, np.complex64)
    out.real, out.imag = q(Y.real, kre), q(Y.imag, kim)
    return out


def _bits_of(a):
    return np.asarray(a, np.complex64).view(np.uint32)


@pytest.mark.parametrize("cfg", [WAVELET_SMOKE, CONFIG], ids=["64", "256"])
def test_band_map_matches_numpy(cfg):
    op, _ = _setup(cfg)
    want = plain_bands(op.kspace_op.indices, cfg.resolution, N_BANDS)
    np.testing.assert_array_equal(np.asarray(kspace_radial_bands(op, n_bands=N_BANDS)), want)
    raw = kspace_radial_bands(op.kspace_op.indices, cfg.resolution, N_BANDS)
    np.testing.assert_array_equal(np.asarray(raw), want)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("rows", [1, 8])
@pytest.mark.parametrize("cfg", [WAVELET_SMOKE, CONFIG], ids=["64", "256"])
def test_per_band_equals_plain_quantizer_bit_for_bit(cfg, rows, bits):
    op, Y = _setup(cfg)
    key = jax.random.PRNGKey(1000 + 10 * rows + bits)
    y = Y[0] if rows == 1 else Y
    got = quantize_observations(y, bits, key, granularity="per_band", op=op,
                                n_bands=N_BANDS)
    assert got.shape == y.shape and got.dtype == jnp.complex64
    band = plain_bands(op.kspace_op.indices, cfg.resolution, N_BANDS)
    want = plain_per_band(np.atleast_2d(np.asarray(y)), bits, key, band, N_BANDS)
    np.testing.assert_array_equal(_bits_of(np.atleast_2d(np.asarray(got))), _bits_of(want))


@pytest.mark.parametrize("cfg", [WAVELET_SMOKE, CONFIG], ids=["64", "256"])
def test_batch_row_equals_the_single_row_call(cfg):
    op, Y = _setup(cfg)
    key = jax.random.PRNGKey(77)
    kw = dict(granularity="per_band", op=op, n_bands=N_BANDS)
    batch = np.asarray(quantize_observations(Y, 8, key, **kw))
    for b in range(Y.shape[0]):
        single = quantize_observations(Y[b], 8, key, **kw)
        np.testing.assert_array_equal(_bits_of(batch[b]), _bits_of(single))


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("cfg", [WAVELET_SMOKE, CONFIG], ids=["64", "256"])
def test_one_band_is_the_per_tensor_quantizer_of_each_row(cfg, bits):
    """One band: each slice takes the paper's single c_y, its largest |re|,
    |im|, and the same draws as the per-tensor quantizer of that slice."""
    op, Y = _setup(cfg)
    key = jax.random.PRNGKey(500 + bits)
    batch = np.asarray(quantize_observations(Y, bits, key, granularity="per_band",
                                             op=op, n_bands=1))
    for b in range(Y.shape[0]):
        single = quantize_observations(Y[b], bits, key)
        np.testing.assert_array_equal(_bits_of(batch[b]), _bits_of(single))


def _program(op, Y):
    return jax.jit(lambda y, k: quantize_observations(
        y, 8, k, granularity="per_band", op=op, n_bands=N_BANDS)).lower(
            Y, jax.random.PRNGKey(0))


def test_program_has_no_scatter_and_no_sort():
    op, Y = _setup(WAVELET_SMOKE)
    lowered = _program(op, Y)
    ops = set(re.findall(r"\bstablehlo\.([a-z_]+)", lowered.as_text()))
    assert ops and not ops & {"scatter", "sort"}, sorted(ops)
    hlo = lowered.compile().as_text()
    opcodes = set(re.findall(r"=\s*\S+\s+([a-z][a-z-]*)\(", hlo))
    assert opcodes and not opcodes & {"scatter", "sort"}, sorted(opcodes)


def _compiles(fn):
    """Backend compiles while ``fn()`` runs to its result."""
    compiles = []

    def listen(event, duration, **_):
        if event == COMPILE_EVENT:
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        jax.block_until_ready(fn())
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    return len(compiles)


def test_second_call_at_the_same_shape_compiles_nothing():
    op, Y = _setup(WAVELET_SMOKE)
    kw = dict(granularity="per_band", op=op, n_bands=N_BANDS)
    k1, k2 = jax.random.PRNGKey(3), jax.random.PRNGKey(4)
    Y2 = Y * 2.0
    jax.block_until_ready(quantize_observations(Y, 8, k1, **kw))
    assert _compiles(lambda: quantize_observations(Y2, 8, k2, **kw)) == 0


def test_a_new_shape_compiles_one_program():
    op, Y = _setup(WAVELET_SMOKE)
    kw = dict(granularity="per_band", op=op, n_bands=N_BANDS)
    key, Y3 = jax.random.PRNGKey(5), Y[:3]
    assert _compiles(lambda: quantize_observations(Y3, 8, key, **kw)) == 1
