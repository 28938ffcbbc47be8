"""MRI application substrate: the paper's second workload (§5, "samples of
brain images") — recovery from aggressively quantized subsampled-Fourier
measurements.

An MRI scanner acquires k-space (2D Fourier) coefficients of the image;
compressed sensing undersamples k-space to cut scan time, and the paper's
low-precision angle quantizes the acquired samples (``bits_y``) before
recovery. The sensing model is Φ = P_Ω F (orthonormal 2D DFT + sampling mask),
implemented matrix-free by
:class:`~repro.core.operators.SubsampledFourierOperator` — at 256×256 the
dense partial-Fourier matrix would be ~2 GB, so only the implicit form makes
this workload reachable.

Real anatomy is NOT pixel-sparse — the paper's brain images are sparse in a
*wavelet* basis. ``make_mri_problem(sparsity_basis="haar"|"db4")`` therefore
recovers the **full, unsparsified** phantom through the composed model
Φ = P_Ω F W†
(:class:`~repro.core.operators.ComposedOperator` of the Fourier factor with a
:class:`~repro.core.operators.WaveletSynthesisOperator`): the solver iterates
on the approximately-sparse wavelet coefficient vector, and image-space
quality is read off ``W† x̂`` (``MRIProblem.to_image``). The legacy
``sparsity_basis="pixel"`` keeps the s-sparsified phantom of the exact-sparsity
guarantees.

This module provides the non-operator half of the pipeline:

* phantoms — :func:`shepp_logan` (the standard modified Shepp–Logan head
  phantom) and :func:`brain_phantom` (randomized brain-like piecewise-constant
  images: skull ring + random elliptical "tissue" regions),
* :func:`sparsify_image` — the s-sparse phantom the pixel-basis solver
  recovers exactly; :func:`wavelet_coeffs` — the transform-domain signal the
  wavelet bases iterate on,
* sampling masks — :func:`cartesian_mask` with ``density="uniform"`` or
  ``"variable"`` (polynomial density concentrating samples at low frequencies,
  the standard CS-MRI pattern) and an always-sampled center block,
* :func:`mri_observations` / :func:`quantize_observations` — noisy k-space
  samples and the b_y-bit stochastic quantization applied to them. The
  quantizer scale is per-tensor (the paper's single c_y) by default, or
  **per-band**: concentric radial bands of k-space each carry their own scale
  (:func:`kspace_radial_bands` / :func:`kspace_band_scales`), matching
  quantizer resolution to the steeply decaying spectral energy of images —
  the single shared scale is what collapses b_y < 8 (huge DC coefficients
  force tiny high-frequency samples under the rounding step; see
  BENCH_mri.json int4/int2 rows),
* :func:`make_mri_problem` — one call bundling all of the above.

Masks are generated in *centered* coordinates (DC in the middle, how k-space
is drawn in the MRI literature) and ifft-shifted to the DC-at-[0,0] convention
``SubsampledFourierOperator``'s ``fft2`` uses.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.operators import (
    ComposedOperator,
    SubsampledFourierOperator,
    WaveletSynthesisOperator,
)
from repro.quant.formats import BY_BITS
from repro.quant.quantize import fake_quantize, quantize_codes
from repro.transforms.wavelet import dwt2, flatten_coeffs

# Modified Shepp–Logan (Toft): (intensity, a, b, x0, y0, angle_deg) per ellipse.
_SHEPP_LOGAN = (
    (1.00, 0.6900, 0.9200, 0.00, 0.0000, 0.0),
    (-0.80, 0.6624, 0.8740, 0.00, -0.0184, 0.0),
    (-0.20, 0.1100, 0.3100, 0.22, 0.0000, -18.0),
    (-0.20, 0.1600, 0.4100, -0.22, 0.0000, 18.0),
    (0.10, 0.2100, 0.2500, 0.00, 0.3500, 0.0),
    (0.10, 0.0460, 0.0460, 0.00, 0.1000, 0.0),
    (0.10, 0.0460, 0.0460, 0.00, -0.1000, 0.0),
    (0.10, 0.0460, 0.0230, -0.08, -0.6050, 0.0),
    (0.10, 0.0230, 0.0230, 0.00, -0.6060, 0.0),
    (0.10, 0.0230, 0.0460, 0.06, -0.6050, 0.0),
)


def _render_ellipses(resolution: int, ellipses) -> np.ndarray:
    """Sum of constant-intensity ellipses on the [-1, 1]² grid → (r, r) f32."""
    lin = np.linspace(-1.0, 1.0, resolution)
    xx, yy = np.meshgrid(lin, lin, indexing="xy")
    img = np.zeros((resolution, resolution), np.float32)
    for inten, a, b, x0, y0, ang in ellipses:
        th = np.deg2rad(ang)
        xr = (xx - x0) * np.cos(th) + (yy - y0) * np.sin(th)
        yr = -(xx - x0) * np.sin(th) + (yy - y0) * np.cos(th)
        img += np.float32(inten) * ((xr / a) ** 2 + (yr / b) ** 2 <= 1.0)
    return np.clip(img, 0.0, None)


def shepp_logan(resolution: int) -> jax.Array:
    """The modified Shepp–Logan head phantom, (r, r) float32 in [0, 1]."""
    return jnp.asarray(_render_ellipses(resolution, _SHEPP_LOGAN))


def brain_phantom(
    resolution: int,
    key: jax.Array,
    n_regions: int = 8,
) -> jax.Array:
    """A randomized brain-like piecewise-constant image, (r, r) float32.

    Skull: a bright outer ellipse ring (like Shepp–Logan's). Interior:
    ``n_regions`` random ellipses of random constant intensity — the
    piecewise-constant structure of anatomical images, with randomized
    geometry so experiments average over phantoms instead of overfitting the
    one canonical image.
    """
    seed = int(jax.random.randint(key, (), 0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    ellipses = [(1.0, 0.72, 0.92, 0.0, 0.0, 0.0),
                (-0.75, 0.67, 0.86, 0.0, 0.0, 0.0)]
    for _ in range(n_regions):
        a = rng.uniform(0.05, 0.35)
        b = rng.uniform(0.05, 0.35)
        # keep the region inside the skull interior
        x0 = rng.uniform(-0.45, 0.45)
        y0 = rng.uniform(-0.55, 0.55)
        ellipses.append((rng.uniform(-0.2, 0.4), a, b, x0, y0, rng.uniform(0, 180)))
    return jnp.asarray(np.clip(_render_ellipses(resolution, ellipses), 0.0, 1.0))


def sparsify_image(img: jax.Array, s: int) -> jax.Array:
    """Keep the s largest-magnitude pixels: the s-sparse phantom, as an (r²,)
    vector (the exact-sparsity signal model of the recovery guarantees)."""
    flat = img.ravel()
    vals, idx = jax.lax.top_k(jnp.abs(flat), s)
    del vals
    return jnp.zeros_like(flat).at[idx].set(flat[idx])


def wavelet_coeffs(img: jax.Array, wavelet: str = "haar",
                   levels: Optional[int] = None) -> jax.Array:
    """W img: the (approximately sparse) wavelet coefficient vector ``(r²,)``
    of an ``(r, r)`` image — the transform-domain signal the Φ = P_Ω F W†
    model recovers. No thresholding happens here: the anatomy is kept whole,
    and sparsity is a property the solver's H_s exploits, not one we impose."""
    return flatten_coeffs(dwt2(img, wavelet, levels))


def cartesian_mask(
    resolution: int,
    fraction: float,
    key: jax.Array,
    density: str = "variable",
    center_fraction: float = 0.04,
    power: float = 3.0,
) -> np.ndarray:
    """A Cartesian k-space sampling mask, (r, r) boolean, DC at [0, 0].

    ``fraction`` of the r² grid points are sampled: a fully-sampled center
    block covering ``center_fraction`` of k-space (low frequencies hold most
    image energy — every practical CS-MRI pattern keeps them), plus random
    points drawn ``density="uniform"``-ly or with ``"variable"`` density
    ∝ (1 − d/d_max)^power (more samples near the center, the standard
    variable-density scheme). Returned in the unshifted convention
    :class:`~repro.core.operators.SubsampledFourierOperator` expects.
    """
    if not 0.0 < fraction <= 1.0:
        raise ValueError(f"fraction must be in (0, 1], got {fraction}")
    if density not in ("uniform", "variable"):
        raise ValueError(f"unknown density {density!r} (use 'uniform' or 'variable')")
    r = resolution
    n_total = max(1, int(round(fraction * r * r)))

    # centered coordinates: distance of each grid point from DC
    lin = np.arange(r) - r // 2
    xx, yy = np.meshgrid(lin, lin, indexing="ij")
    dist = np.sqrt(xx**2 + yy**2) / np.sqrt(2.0) / (r // 2)

    mask = np.zeros((r, r), bool)
    half_c = max(1, int(round(np.sqrt(center_fraction) * r / 2)))
    c = r // 2
    mask[c - half_c:c + half_c, c - half_c:c + half_c] = True
    if int(mask.sum()) > n_total:
        raise ValueError(
            f"center block ({int(mask.sum())} samples) exceeds the requested "
            f"fraction ({n_total} samples); lower center_fraction below {fraction}")

    n_rand = n_total - int(mask.sum())
    if n_rand > 0:
        free = np.flatnonzero(~mask.ravel())
        if density == "uniform":
            p = np.ones(free.size)
        else:
            p = np.maximum(1.0 - np.clip(dist.ravel()[free], 0.0, 1.0), 1e-3) ** power
        p = p / p.sum()
        seed = int(jax.random.randint(key, (), 0, 2**31 - 1))
        rng = np.random.default_rng(seed)
        pick = rng.choice(free, size=min(n_rand, free.size), replace=False, p=p)
        # .flat (not .ravel()): ravel() writes through only while the array
        # stays contiguous — .flat is the spelling that cannot silently become
        # a copy if the allocation above ever changes.
        mask.flat[pick] = True
    return np.fft.ifftshift(mask)


def kspace_radial_bands(
    op_or_indices,
    resolution: Optional[int] = None,
    n_bands: int = 8,
) -> jax.Array:
    """Radial band index (0 = DC … n_bands-1 = corners) per k-space sample.

    Accepts anything exposing the k-space geometry — a
    :class:`~repro.core.operators.SubsampledFourierOperator` or a composition
    Φ = P_Ω F W† (unwrapped through its ``kspace_op`` property) — or a flat
    index array (with ``resolution``). Indices follow the unshifted
    DC-at-[0,0] convention the operator's ``fft2`` uses; bands are concentric
    annuli of equal radial width on the centered grid.
    """
    op_or_indices = getattr(op_or_indices, "kspace_op", op_or_indices)
    if isinstance(op_or_indices, SubsampledFourierOperator):
        idx, r = op_or_indices.indices, op_or_indices.resolution
    else:
        if resolution is None:
            raise ValueError("resolution required when passing raw indices")
        idx, r = jnp.asarray(op_or_indices, jnp.int32), int(resolution)
    if n_bands < 1:
        raise ValueError(f"n_bands must be >= 1, got {n_bands}")
    row, col = idx // r, idx % r
    # unshifted index -> signed frequency in [-r/2, r/2)
    fr = ((row + r // 2) % r) - r // 2
    fc = ((col + r // 2) % r) - r // 2
    dist = jnp.sqrt((fr.astype(jnp.float32)) ** 2 + (fc.astype(jnp.float32)) ** 2)
    # opaque to XLA's folding, so a compiled map rounds as the op-by-op one:
    # folded into one factor n_bands/d_max, it moves samples on a band edge
    two, nb = jax.lax.optimization_barrier((jnp.float32(2.0), jnp.float32(n_bands)))
    d_max = jnp.sqrt(two) * (r / 2.0)
    band = jnp.floor(dist / d_max * nb).astype(jnp.int32)
    return jnp.clip(band, 0, n_bands - 1)


def kspace_band_scales(y: jax.Array, bands: jax.Array, n_bands: int) -> jax.Array:
    """Per-band quantizer scale: max component magnitude within each radial
    band (real & imaginary share one scale, like the per-tensor quantizer).
    ``y`` is (M,) or batched (..., M); returns (..., n_bands) f32, with empty
    or all-zero bands guarded to scale 1.

    A max over a one-hot (n_bands, M) view, not a scatter: exact, so equal to
    a segment max bit for bit, and no sort on the TPU."""
    mag = jnp.maximum(jnp.abs(jnp.real(y)), jnp.abs(jnp.imag(y)))
    in_band = bands == jnp.arange(n_bands, dtype=bands.dtype)[:, None]
    s = jnp.max(jnp.where(in_band, mag[..., None, :], -jnp.inf), axis=-1)
    return jnp.where(s > 0, s, jnp.ones_like(s))  # also clears -inf empties


@functools.partial(jax.jit, static_argnames=("resolution", "bits_y", "n_bands"))
def _quantize_per_band(y, indices, key, resolution, bits_y, n_bands):
    """The per-band quantizer as one program per (shape, bits, n_bands), the
    band map included."""
    with jax.named_scope("yquant"):
        bands = kspace_radial_bands(indices, resolution, n_bands)
        scales = kspace_band_scales(y, bands, n_bands)          # (..., n_bands)
        kre, kim = jax.random.split(key)

        def one(y_row, scale_row):
            """One acquisition; every batch row folds the same key so that
            row b of a batched call reproduces the single-row call
            bit-for-bit (the qniht batching contract)."""
            s = scale_row[bands]
            cre, _ = quantize_codes(jnp.real(y_row), bits_y, kre, scale=s)
            cim, _ = quantize_codes(jnp.imag(y_row), bits_y, kim, scale=s)
            step = s / BY_BITS[bits_y].half_steps
            return jax.lax.complex(cre.astype(jnp.float32) * step,
                                   cim.astype(jnp.float32) * step)

        flat_y = y.reshape(-1, y.shape[-1])
        flat_s = scales.reshape(-1, n_bands)
        return jax.vmap(one)(flat_y, flat_s).reshape(y.shape).astype(y.dtype)


def quantize_observations(
    y: jax.Array,
    bits_y: int,
    key: jax.Array,
    granularity: str = "per_tensor",
    op=None,
    n_bands: int = 8,
) -> jax.Array:
    """The paper's b_y-bit stochastic quantization of acquired k-space samples
    (complex: real/imag quantized component-wise on a shared scale).
    ``op`` is the sensing operator owning the k-space geometry — a bare
    :class:`~repro.core.operators.SubsampledFourierOperator` or the composed
    Φ = P_Ω F W† (its ``kspace_op`` factor is used).

    ``granularity="per_tensor"`` (default) is the paper's single c_y — one
    scale for all of k-space, identical to ``fake_quantize``.
    ``granularity="per_band"`` carries one scale per concentric radial band
    (``n_bands`` of them, geometry from ``op``): each sample rounds with the
    step of its *local* dynamic range, so the huge low-frequency coefficients
    no longer force the quantization step of the tiny high frequencies. Stream
    overhead is ``4 * n_bands`` bytes of f32 scales (band indices are derivable
    from the sampling mask the acquisition already stores). It runs as one
    compiled program per (shape, ``bits_y``, ``n_bands``) under the named
    scope ``yquant``, the band map included. With ``n_bands=1`` each row
    takes one scale, its largest component: the paper's single c_y per
    acquisition, equal bit for bit to the per-tensor quantizer of that row
    wherever neither its real nor its imaginary part is all zero.
    """
    if granularity == "per_tensor":
        return fake_quantize(y, bits_y, key)
    if granularity != "per_band":
        raise ValueError(
            f"unknown observation granularity {granularity!r} "
            "(use 'per_tensor' or 'per_band')")
    if op is None:
        raise ValueError("per_band quantization needs the sensing operator "
                         "(op=...) for the k-space band geometry")
    kop = getattr(op, "kspace_op", op)
    return _quantize_per_band(y, kop.indices, key, resolution=kop.resolution,
                              bits_y=bits_y, n_bands=n_bands)


def mri_observations(
    op: SubsampledFourierOperator,
    x: jax.Array,
    snr_db: Optional[float],
    key: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """y = Φx + e with circularly-symmetric complex Gaussian acquisition noise
    at the given per-problem SNR (None → noiseless). Returns (y, e).

    ``x`` may be a single (N,) image or a (B, N) batch — the batch is served by
    one batched FFT and gets independent per-row noise at the same SNR."""
    clean = op.mv(x)
    if snr_db is None:
        return clean, jnp.zeros_like(clean)
    m = clean.shape[-1]
    sig_pow = jnp.real(jnp.sum(clean * jnp.conj(clean), axis=-1, keepdims=True))
    sigma = jnp.sqrt(sig_pow / (10.0 ** (snr_db / 10.0)) / m / 2.0)
    kr, ki = jax.random.split(key)
    e = (sigma * (jax.random.normal(kr, clean.shape, jnp.float32)
                  + 1j * jax.random.normal(ki, clean.shape, jnp.float32))
         ).astype(clean.dtype)
    return clean + e, e


SPARSITY_BASES = ("pixel", "haar", "db4")


@dataclasses.dataclass  # jaxlint: allow=JL005 -- host-side bundle of one problem; its arrays enter jit one by one, never the dataclass
class MRIProblem:
    """One subsampled-Fourier recovery instance (matrix-free Φ throughout).

    ``op`` is the operator the solver sees: P_Ω F for the pixel basis, the
    composed P_Ω F W† for a wavelet basis — ``x_true`` correspondingly lives
    in pixel or wavelet-coefficient space. ``image_true`` is always the
    image-space ground truth (= the *full* phantom for wavelet bases, the
    s-sparsified one for pixel); judge recovered iterates against it via
    :meth:`to_image`, never against ``x_true`` in coefficient space.
    """

    op: object            # operator-protocol Φ (matrix-free)
    y: jax.Array          # (M,) complex64 k-space samples (noisy, unquantized)
    e: jax.Array          # (M,) acquisition noise actually added
    x_true: jax.Array     # (r²,) ground truth in the solver's basis
    resolution: int
    s: int
    sparsity_basis: str = "pixel"
    image_true: Optional[jax.Array] = None   # (r²,) image-space ground truth
    synthesis: Optional[WaveletSynthesisOperator] = None

    def __post_init__(self):
        if self.image_true is None:
            self.image_true = self.x_true

    def to_image(self, x: jax.Array) -> jax.Array:
        """Map solver-basis vector(s) ``(…, r²)`` to image space (W† x for
        wavelet bases; identity for pixel). Real part only — the recovered
        image is real by model."""
        if self.synthesis is not None:
            x = self.synthesis.mv(x)
        return jnp.real(x)


def make_mri_problem(
    resolution: int,
    s: int,
    fraction: float,
    key: jax.Array,
    density: str = "variable",
    center_fraction: float = 0.04,
    snr_db: Optional[float] = None,
    phantom: str = "shepp-logan",
    sparsity_basis: str = "pixel",
    wavelet_levels: Optional[int] = None,
) -> MRIProblem:
    """Phantom → truth in the chosen basis → mask → operator → observations.

    ``phantom="shepp-logan"`` uses the canonical head phantom;
    ``"brain"`` draws a randomized piecewise-constant brain-like image from
    ``key``.

    ``sparsity_basis="pixel"`` (default) is the exact-sparsity model: the
    phantom is thresholded to its s largest pixels and sensed through
    Φ = P_Ω F. ``"haar"``/``"db4"`` is the paper's actual §5 scenario: the
    **full** phantom is kept, ``x_true`` becomes its wavelet coefficient
    vector (approximately sparse — Shepp–Logan puts >99.99% of its energy in
    ~12% of its Haar coefficients at 128²), and the operator becomes the
    composition Φ = P_Ω F W†. Observations are always taken in k-space from
    the image the scanner would actually see.

    Quantization of ``y`` is left to the solver's ``bits_y`` (one stochastic
    draw inside ``qniht``, Algorithm-1-faithful); use
    :func:`quantize_observations` to materialize ŷ standalone.
    """
    if sparsity_basis not in SPARSITY_BASES:
        raise ValueError(
            f"unknown sparsity_basis {sparsity_basis!r} (use one of {SPARSITY_BASES})")
    kimg, kmask, knoise = jax.random.split(key, 3)
    if phantom == "shepp-logan":
        img = shepp_logan(resolution)
    elif phantom == "brain":
        img = brain_phantom(resolution, kimg)
    else:
        raise ValueError(f"unknown phantom {phantom!r} (use 'shepp-logan' or 'brain')")
    mask = cartesian_mask(resolution, fraction, kmask, density, center_fraction)
    fourier = SubsampledFourierOperator.from_mask(mask)
    if sparsity_basis == "pixel":
        x_true = sparsify_image(img, s)
        y, e = mri_observations(fourier, x_true, snr_db, knoise)
        return MRIProblem(op=fourier, y=y, e=e, x_true=x_true,
                          resolution=resolution, s=s)
    synthesis = WaveletSynthesisOperator(resolution, sparsity_basis, wavelet_levels)
    image_true = img.ravel()
    x_true = wavelet_coeffs(img, sparsity_basis, synthesis.levels)
    # the scanner samples k-space of the IMAGE; op.mv(x_true) equals this up
    # to the (exact) W†W round trip
    y, e = mri_observations(fourier, image_true, snr_db, knoise)
    return MRIProblem(op=ComposedOperator(fourier, synthesis), y=y, e=e,
                      x_true=x_true, resolution=resolution, s=s,
                      sparsity_basis=sparsity_basis, image_true=image_true,
                      synthesis=synthesis)
