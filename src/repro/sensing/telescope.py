"""Radio-interferometer pipeline: station geometry → measurement matrix Φ.

Follows the paper's supplementary §7 exactly:

* L antennas at positions p_i (meters); all L² ordered pairs (i,k) form baselines
  u_{ik} = (p_i − p_k)/λ₀  (so M = L², autocorrelations included),
* the sky is a r×r grid of direction cosines (l, m) ∈ [−d, d]²  (N = r²),
* Φ_{z,w} = exp(−j2π ⟨u_{ik}, r_{lm}⟩)    (Eq. 73–75),
* visibilities  y = Φ x + e  with e ~ CN(0, σ_n² I)  (thermal antenna noise).

The grid extent ``d`` is the *instrument-dependent tuning knob* of supplementary
§7.3: shrinking/growing d moves γ = σ_max/σ_min − 1, which is how the paper
engineers γ ≤ 1/16 before choosing the bit width via Lemma 1.

No external data needed: the station layout is a deterministic pseudo-LOFAR
low-band (LBA) layout — uniformly-filled disc, the standard model for LOFAR
core-station LBA fields (CS302-like, 15–80 MHz).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

C_LIGHT = 299_792_458.0  # m/s


@dataclasses.dataclass(frozen=True)
class Station:
    """An interferometer station configuration."""

    n_antennas: int = 30
    freq_hz: float = 50e6          # LOFAR low band (15–80 MHz)
    field_radius_m: float = 40.0   # LBA field radius
    seed: int = 302                # CS302 homage; deterministic layout

    @property
    def wavelength(self) -> float:
        return C_LIGHT / self.freq_hz

    include_autocorrelations: bool = False

    @property
    def n_baselines(self) -> int:
        l = self.n_antennas
        return l * l if self.include_autocorrelations else l * (l - 1)

    def antenna_positions(self) -> np.ndarray:
        """(L, 2) meters. Uniform-in-disc, deterministic in ``seed``."""
        rng = np.random.default_rng(self.seed)
        r = self.field_radius_m * np.sqrt(rng.uniform(size=self.n_antennas))
        th = rng.uniform(0, 2 * np.pi, size=self.n_antennas)
        return np.stack([r * np.cos(th), r * np.sin(th)], axis=1)

    def baselines(self) -> np.ndarray:
        """(M, 2) baselines in wavelengths: u_{ik} = (p_i − p_k)/λ.

        Autocorrelations (i = k, u = 0) are excluded by default: they are L
        duplicated zero rows of Φ (rank-deficient → γ = ∞) and in practice are
        discarded anyway (noise-dominated). The paper's M = L² counts them; set
        ``include_autocorrelations=True`` for the literal formulation.
        """
        p = self.antenna_positions()
        d = (p[:, None, :] - p[None, :, :]) / self.wavelength
        d = d.reshape(-1, 2)
        if not self.include_autocorrelations:
            l = self.n_antennas
            mask = ~np.eye(l, dtype=bool).ravel()
            d = d[mask]
        return d


def sky_grid(resolution: int, extent: float = 0.4) -> np.ndarray:
    """(r², 2) direction cosines (l, m) on a regular grid over [−d, d]²."""
    lin = np.linspace(-extent, extent, resolution)
    ll, mm = np.meshgrid(lin, lin, indexing="ij")
    return np.stack([ll.ravel(), mm.ravel()], axis=1)


def measurement_matrix(
    station: Station, resolution: int, extent: float = 0.4, dtype=jnp.complex64
) -> jax.Array:
    """Φ ∈ C^{L² × r²}: Φ_{z,w} = exp(−j2π ⟨u_z, r_w⟩)   (Eq. 75)."""
    uv = jnp.asarray(station.baselines(), dtype=jnp.float32)         # (M, 2)
    grid = jnp.asarray(sky_grid(resolution, extent), dtype=jnp.float32)  # (N, 2)
    # two-term inner products written out: a matmul at the TPU's default
    # precision would round the coordinates to bf16 (phases off by ~0.4 rad)
    phase = -2.0 * jnp.pi * (uv[:, :1] * grid[:, 0] + uv[:, 1:] * grid[:, 1])
    return jnp.exp(1j * phase.astype(jnp.float32)).astype(dtype)


def visibilities(
    phi: jax.Array,
    x: jax.Array,
    snr_db: Optional[float],
    key: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """y = Φx + e with circularly-symmetric complex Gaussian noise at the given
    *antenna-level* SNR (paper §4 uses 0 dB). Returns (y, e)."""
    clean_y = jnp.matmul(phi, x.astype(phi.dtype), precision=jax.lax.Precision.HIGHEST)
    if snr_db is None:
        return clean_y, jnp.zeros_like(clean_y)
    m = clean_y.shape[0]
    sig_pow = jnp.real(jnp.vdot(clean_y, clean_y))
    noise_pow = sig_pow / (10.0 ** (snr_db / 10.0))
    sigma = jnp.sqrt(noise_pow / m / 2.0)
    kr, ki = jax.random.split(key)
    e = sigma * (
        jax.random.normal(kr, (m,), jnp.float32)
        + 1j * jax.random.normal(ki, (m,), jnp.float32)
    ).astype(phi.dtype)
    return clean_y + e, e


def dirty_image(phi: jax.Array, y: jax.Array, resolution: int) -> jax.Array:
    """Least-squares/backprojection estimate Re(Φ†y) (the 'dirty image')."""
    x = jnp.real(jnp.conj(phi.T) @ y) / phi.shape[0]
    return x.reshape(resolution, resolution)


def dirty_beam(phi: jax.Array, resolution: int) -> jax.Array:
    """PSF: backprojection of the response to a unit source at the grid center."""
    n = resolution * resolution
    center = (resolution // 2) * resolution + resolution // 2
    delta = jnp.zeros((n,), dtype=phi.dtype).at[center].set(1.0)
    return dirty_image(phi, phi @ delta, resolution)


def tune_extent_for_gamma(
    station: Station,
    resolution: int,
    extents: np.ndarray,
    target: float = 1.0 / 16.0,
):
    """Supplementary §7.3 / Fig. 7: sweep the grid extent d and report γ(d).

    Returns a list of (d, gamma) and the largest d meeting γ ≤ target (or None).
    """
    from repro.core.rip import gamma_full

    results = []
    best = None
    for d in extents:
        phi = measurement_matrix(station, resolution, float(d))
        g = float(gamma_full(phi))
        results.append((float(d), g))
        if g <= target:
            best = float(d) if best is None else max(best, float(d))
    return results, best
