"""The served MRI solve against a plain reference.

``BatchServer.submit`` over Φ = P_Ω F W† (``ComposedOperator`` of the
subsampled Fourier operator and the Haar synthesis operator) at the
``WAVELET_SMOKE`` sizes (64², s = 500, 25 iterations), on brain slices whose
k-space samples are quantized to 8 bits per radial band, and with one scale
a slice (the paper's c_y) as the benchmark's MRI cell does. The reference is a
plain float32 NIHT written here: ``jnp.fft`` for the Fourier factor, a
periodized orthonormal Haar pyramid made of matrix products, no code of the
program's operators, solver or server. Both sides get the same quantized y.

Per row, gap = ‖x − x_ref‖ / ‖x_ref‖. Two runs of one algorithm on the same
data differ by rounding, and where rounding flips one support decision the
trajectories part, so the test holds a median and a share of rows, not a
maximum. The same reference on bfloat16 data, one precision below the f32 the
configuration states, has to fail the same tolerances.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.mri_brain import WAVELET_SMOKE as CFG
from repro.core.operators import (
    ComposedOperator,
    SubsampledFourierOperator,
    WaveletSynthesisOperator,
)
from repro.parallel import BatchServer, make_batch_mesh
from repro.sensing import brain_phantom, cartesian_mask, quantize_observations

ROWS = 8
SOLVER = dict(c=0.01, shrink_k=2.0, max_backtracks=30)
# On the CPU the program's rows sit at 0.9e-7 to 1.6e-7 of the reference
# (float32 rounding of the same steps); on bfloat16 data every row moves by
# 9e-3 or more (median 4e-2; with one band 1.0e-7 to 1.6e-7, and 3.3e-2 or
# more). 1e-5 sits 60x above the first, 900x below the second.
MEDIAN_GAP = 1e-5
OFF_GAP = 1e-2      # a row this far from the reference took another support
# One row of eight may part where rounding flips a support decision; the
# program parts none, bfloat16 data parts seven of eight at this size.
ROWS_OFF = 0.125


# -- the plain reference ----------------------------------------------------

def haar_matrix(m):
    """(m, m) one level of the orthonormal periodized Haar analysis: m/2
    averages of neighbouring pairs, then m/2 differences."""
    a = np.zeros((m, m), np.float32)
    h = m // 2
    s = 1.0 / math.sqrt(2.0)
    for k in range(h):
        a[k, 2 * k] = a[k, 2 * k + 1] = s
        a[h + k, 2 * k], a[h + k, 2 * k + 1] = s, -s
    return jnp.asarray(a, dtype=a.dtype)


def levels(r):
    return int(round(math.log2(r)))


def haar_analysis(img):
    """Full Haar pyramid of real (B, r, r) images: each level transforms the
    rows and columns of the current top-left block."""
    r = img.shape[-1]
    for lv in range(levels(r)):
        m = r >> lv
        a = haar_matrix(m)
        img = img.at[:, :m, :m].set(a @ img[:, :m, :m] @ a.T)
    return img


def haar_synthesis(co):
    """Inverse of :func:`haar_analysis` (its transpose: the matrices are
    orthonormal)."""
    r = co.shape[-1]
    for lv in reversed(range(levels(r))):
        m = r >> lv
        a = haar_matrix(m)
        co = co.at[:, :m, :m].set(a.T @ co[:, :m, :m] @ a)
    return co


def bf16(v):
    def rnd(a):
        # jaxlint: allow=JL001 -- the control rounds to bfloat16 on purpose
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    if jnp.iscomplexobj(v):
        return jax.lax.complex(rnd(jnp.real(v)), rnd(jnp.imag(v)))
    return rnd(v)


def sq(v):
    return jnp.sum(jnp.real(v) ** 2 + jnp.imag(v) ** 2, axis=-1)


def top_s(v, s):
    _, idx = jax.lax.top_k(jnp.abs(v), s)
    return jnp.zeros(v.shape, bool).at[jnp.arange(v.shape[0])[:, None], idx].set(True)


def sample(img, idx):
    """P_Ω F of real (B, r, r) images: (B, M) complex64."""
    k = jnp.fft.fft2(img, norm="ortho")
    return k.reshape(img.shape[0], -1)[:, idx]


def reference(Yq, idx, r, s, n_iters, lowp=False):
    """Plain f32 NIHT of each row of ``Yq`` (B, M) through P_Ω F W†, real
    signal; every value rounded to bfloat16 with ``lowp``."""
    with jax.default_matmul_precision("highest"):
        return _reference(Yq, idx, r=r, s=s, n_iters=n_iters, lowp=lowp)


@functools.partial(jax.jit, static_argnames=("r", "s", "n_iters", "lowp"))
def _reference(Yq, idx, *, r, s, n_iters, lowp):
    rnd = bf16 if lowp else (lambda v: v)
    n = r * r
    b = Yq.shape[0]
    c, shrink, max_bt = SOLVER["c"], SOLVER["shrink_k"], SOLVER["max_backtracks"]

    def mv(X):
        return rnd(sample(haar_synthesis(rnd(X).reshape(b, r, r)), idx))

    def rmv(R):
        full = jnp.zeros((b, n), jnp.complex64).at[:, idx].set(rnd(R))
        img = jnp.fft.ifft2(full.reshape(b, r, r), norm="ortho")
        co = jax.lax.complex(haar_analysis(jnp.real(img)), haar_analysis(jnp.imag(img)))
        return rnd(co.reshape(b, n))

    Y = rnd(Yq)

    def h_s(a):
        return jnp.where(top_s(a, s), a, 0.0)

    def step(_, X):
        G = rmv(Y - mv(X))
        gamma = jnp.where((sq(X) == 0.0)[:, None], top_s(G, s), X != 0)
        mu0 = sq(jnp.where(gamma, G, 0)) / (sq(mv(jnp.where(gamma, G, 0))) + 1e-30)

        def propose(mu):
            return h_s(jnp.real(X + mu[:, None] * G))

        def accepted(mu, Xp):
            D = Xp - X
            return (jnp.all((Xp != 0) == gamma, axis=-1)
                    | (mu <= (1.0 - c) * sq(D) / (sq(mv(D)) + 1e-30)))

        def cond(st):
            return jnp.any(st[3] & (st[2] < max_bt))

        def body(st):
            mu, Xp, tries, todo = st
            live = todo & (tries < max_bt)
            mu = jnp.where(live, mu / (shrink * (1.0 - c)), mu)
            Xp = jnp.where(live[:, None], propose(mu), Xp)
            return mu, Xp, tries + live, live & ~accepted(mu, Xp)

        X0 = propose(mu0)
        return jax.lax.while_loop(
            cond, body, (mu0, X0, jnp.zeros(b, jnp.int32), ~accepted(mu0, X0)))[1]

    return jax.lax.fori_loop(0, n_iters, step, jnp.zeros((b, n), jnp.float32))


def gaps(x, ref):
    x = np.asarray(x, np.float64).reshape(ref.shape[0], -1)
    ref = np.asarray(ref, np.float64)
    return np.linalg.norm(x - ref, axis=1) / np.linalg.norm(ref, axis=1)


# -- the served solve -------------------------------------------------------

def _served(n_bands):
    """The program's answers, the reference's inputs and its f32 answers,
    with y quantized in ``n_bands`` radial bands."""
    r = CFG.resolution
    kmask = jax.random.split(jax.random.PRNGKey(CFG.seed), 3)[1]
    mask = cartesian_mask(r, CFG.fraction, kmask, CFG.density, CFG.center_fraction)
    idx = jnp.asarray(np.flatnonzero(mask.ravel()), jnp.int32)
    op = ComposedOperator(SubsampledFourierOperator.from_mask(mask),
                          WaveletSynthesisOperator(r, "haar"))
    imgs = jnp.stack([brain_phantom(r, jax.random.PRNGKey(60 + b)) for b in range(ROWS)])
    key = jax.random.PRNGKey(61)
    Yq = quantize_observations(sample(imgs, idx), CFG.bits_y, key,
                               granularity="per_band", op=op, n_bands=n_bands)
    server = BatchServer(op, CFG.n_sparse, CFG.n_iters, mesh=make_batch_mesh(1),
                         real_signal=True, early_exit=True, key=key, **SOLVER)
    x = server.submit(Yq, key=key).x
    ref = reference(Yq, idx, r, CFG.n_sparse, CFG.n_iters)
    return dict(x=x, Yq=Yq, idx=idx, ref=ref)


@pytest.fixture(scope="module")
def served():
    return _served(CFG.n_bands)


@pytest.fixture(scope="module")
def served_single_scale():
    """One band: the paper's single scale c_y a slice, as the benchmark's
    MRI cell quantizes y."""
    return _served(1)


def test_served_solve_matches_the_plain_reference(served):
    g = gaps(served["x"], served["ref"])
    assert np.isfinite(np.asarray(served["x"])).all()
    assert np.median(g) <= MEDIAN_GAP, g
    assert np.mean(g > OFF_GAP) <= ROWS_OFF, g


def test_bfloat16_data_fails_the_same_tolerances(served):
    """The tolerances can see a precision drop: the reference on bfloat16
    data, held to them against its own f32 answers, fails."""
    low = reference(served["Yq"], served["idx"], CFG.resolution, CFG.n_sparse,
                    CFG.n_iters, lowp=True)
    g = gaps(low, served["ref"])
    assert np.median(g) > MEDIAN_GAP and np.mean(g > OFF_GAP) > ROWS_OFF, g


def test_single_scale_served_solve_matches_the_plain_reference(served_single_scale):
    test_served_solve_matches_the_plain_reference(served_single_scale)


def test_single_scale_bfloat16_data_fails_the_same_tolerances(served_single_scale):
    test_bfloat16_data_fails_the_same_tolerances(served_single_scale)
