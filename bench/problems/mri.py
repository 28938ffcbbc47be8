"""MRI slices from subsampled k-space: Phi = P_Omega F W^H, matrix-free.

The benchmark's own generators, after the paper's section 5:

* the sampling mask is the scan protocol: a fully sampled centre block of
  ``center_fraction`` of k-space plus variable-density random points, drawn
  once from the configuration's ``mask_seed`` (DC at [0, 0]);
* a slice is a brain-like phantom: a skull ring and ``n_regions`` random
  ellipses of constant intensity, rendered on the device;
* y = P_Omega F img, orthonormal 2-D DFT, noiseless: the quantization of y is
  the noise under study.

The timed path quantizes each chunk with the program's per-band quantizer
and solves it through ``BatchServer`` on the program's composed operator.
The reference quantizes with its own copy of the per-band quantizer and
recovers with its own operator: ``jnp.fft`` and a plain periodized Haar
transform.
"""
from __future__ import annotations

import math
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import refniht


def mask(cfg):
    """(r, r) boolean k-space mask, DC at [0, 0], from ``mask_seed``."""
    r, frac = cfg["resolution"], cfg["fraction"]
    kmask = jax.random.split(jax.random.PRNGKey(cfg["mask_seed"]), 3)[1]
    n_total = max(1, int(round(frac * r * r)))
    lin = np.arange(r) - r // 2
    xx, yy = np.meshgrid(lin, lin, indexing="ij")
    dist = np.sqrt(xx**2 + yy**2) / np.sqrt(2.0) / (r // 2)
    m = np.zeros((r, r), bool)
    half_c = max(1, int(round(np.sqrt(cfg["center_fraction"]) * r / 2)))
    c = r // 2
    m[c - half_c:c + half_c, c - half_c:c + half_c] = True
    free = np.flatnonzero(~m.ravel())
    p = np.maximum(1.0 - np.clip(dist.ravel()[free], 0.0, 1.0), 1e-3) ** cfg["density_power"]
    rng = np.random.default_rng(int(jax.random.randint(kmask, (), 0, 2**31 - 1)))
    n_rand = n_total - int(m.sum())
    m.flat[rng.choice(free, size=min(n_rand, free.size), replace=False, p=p / p.sum())] = True
    return np.fft.ifftshift(m)


def bands(idx, r, n_bands):
    """Radial band (0 = DC) of each flat k-space index, equal radial widths."""
    fr = ((idx // r + r // 2) % r) - r // 2
    fc = ((idx % r + r // 2) % r) - r // 2
    dist = jnp.sqrt(fr.astype(jnp.float32) ** 2 + fc.astype(jnp.float32) ** 2)
    b = jnp.floor(dist / (jnp.sqrt(2.0) * (r / 2.0)) * n_bands).astype(jnp.int32)
    return jnp.clip(b, 0, n_bands - 1)


PHANTOM_KEYS = ("resolution", "n_regions")


def _phantom(key, resolution, n_regions):
    """One brain-like slice, (r * r,) float32 in [0, 1]."""
    u = jax.random.uniform(key, (n_regions, 6))
    ell = jnp.stack([
        -0.2 + 0.6 * u[:, 0],            # intensity
        0.05 + 0.30 * u[:, 1],           # semi-axis a
        0.05 + 0.30 * u[:, 2],           # semi-axis b
        -0.45 + 0.90 * u[:, 3],          # centre x
        -0.55 + 1.10 * u[:, 4],          # centre y
        jnp.pi * u[:, 5],                # angle
    ], axis=1)
    skull = jnp.asarray([[1.0, 0.72, 0.92, 0.0, 0.0, 0.0],
                         [-0.75, 0.67, 0.86, 0.0, 0.0, 0.0]], jnp.float32)
    ell = jnp.concatenate([skull, ell])
    lin = jnp.linspace(-1.0, 1.0, resolution)
    xx, yy = jnp.meshgrid(lin, lin, indexing="xy")

    def one(e):
        inten, a, b, x0, y0, th = e
        xr = (xx - x0) * jnp.cos(th) + (yy - y0) * jnp.sin(th)
        yr = -(xx - x0) * jnp.sin(th) + (yy - y0) * jnp.cos(th)
        return inten * ((xr / a) ** 2 + (yr / b) ** 2 <= 1.0)

    img = jnp.sum(jax.vmap(one)(ell), axis=0)
    return jnp.clip(img, 0.0, 1.0).ravel()


def fourier(img, idx, r):
    """P_Omega F of (B, r * r) images: (B, M) complex64."""
    k = jnp.fft.fft2(img.reshape(-1, r, r).astype(jnp.complex64), norm="ortho")
    return jnp.take(k.reshape(-1, r * r), idx, axis=-1)


@partial(jax.jit, static_argnames=("n_out",) + PHANTOM_KEYS)
def _make_rows(pool_key, rows, idx, *, n_out, resolution, n_regions):
    keys = jax.vmap(lambda r: jax.random.fold_in(pool_key, r))(rows)
    imgs = jax.vmap(lambda k: _phantom(k, resolution, n_regions))(keys)
    return tuple(jnp.split(fourier(imgs, idx, resolution), n_out))


def make_rows(pool_key, rows, idx, cfg, n_out=1):
    return _make_rows(pool_key, jnp.asarray(rows, jnp.int32), idx, n_out=n_out,
                      resolution=cfg["resolution"], n_regions=cfg["n_regions"])


def quantize_per_band(Y, bits, key, band, n_bands):
    """b-bit stochastic quantization of each row of Y (B, M), one scale per
    radial band (the largest |re|, |im| there); every row folds ``key``."""
    k = 2 ** (bits - 2)
    mag = jnp.maximum(jnp.abs(jnp.real(Y)), jnp.abs(jnp.imag(Y)))
    scale = jax.vmap(lambda m: jax.ops.segment_max(m, band, num_segments=n_bands))(mag)
    scale = jnp.where(scale > 0, scale, 1.0)[:, band]
    kre, kim = jax.random.split(key)

    def q(v, kk):
        scaled = jnp.clip(v / scale, -1.0, 1.0) * k
        low = jnp.floor(scaled)
        u = jax.random.uniform(kk, v.shape[-1:], dtype=jnp.float32)
        return jnp.clip(low + (u < scaled - low).astype(jnp.float32), -k, k)

    step = scale / k
    return jax.lax.complex(q(jnp.real(Y), kre) * step, q(jnp.imag(Y), kim) * step)


def haar_analysis(img, levels):
    """Periodized orthonormal Haar pyramid of (B, r, r) images."""
    s = 1.0 / math.sqrt(2.0)
    out = img
    m = img.shape[-1]
    for _ in range(levels):
        blk = out[:, :m, :m]
        blk = jnp.concatenate([(blk[..., 0::2] + blk[..., 1::2]) * s,
                               (blk[..., 0::2] - blk[..., 1::2]) * s], axis=-1)
        blk = jnp.concatenate([(blk[:, 0::2] + blk[:, 1::2]) * s,
                               (blk[:, 0::2] - blk[:, 1::2]) * s], axis=1)
        out = out.at[:, :m, :m].set(blk)
        m //= 2
    return out


def haar_synthesis(co, levels):
    """Inverse (and adjoint) of :func:`haar_analysis`."""
    s = 1.0 / math.sqrt(2.0)
    out = co
    r = co.shape[-1]
    for lv in reversed(range(levels)):
        m = r >> lv
        h = m // 2
        blk = out[:, :m, :m]
        a, d = blk[:, :h], blk[:, h:]
        blk = jnp.stack([(a + d) * s, (a - d) * s], axis=2).reshape(blk.shape)
        a, d = blk[..., :h], blk[..., h:]
        blk = jnp.stack([(a + d) * s, (a - d) * s], axis=-1).reshape(blk.shape)
        out = out.at[:, :m, :m].set(blk)
    return out


def haar_levels(r):
    lv = 0
    while r % 2 == 0 and r >= 2:
        lv += 1
        r //= 2
    return lv


def to_bf16(v):
    """Round a real or complex array to bfloat16 and back."""
    def rnd(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    if jnp.iscomplexobj(v):
        return jax.lax.complex(rnd(jnp.real(v)), rnd(jnp.imag(v)))
    return rnd(v)


@partial(jax.jit, static_argnames=("r", "lowp", "s", "n_iters", "c", "shrink_k",
                                   "max_backtracks", "real_signal", "nonneg"))
def _solve(Yhat, idx, *, r, lowp, **kw):
    lv = haar_levels(r)
    rnd = to_bf16 if lowp else (lambda v: v)

    def mv(X):
        img = haar_synthesis(rnd(X).reshape(-1, r, r), lv)
        return rnd(fourier(img, idx, r))

    def rmv(R):
        full = jnp.zeros((R.shape[0], r * r), jnp.complex64).at[:, idx].set(rnd(R))
        img = jnp.fft.ifft2(full.reshape(-1, r, r), norm="ortho")
        co = haar_analysis(jnp.real(img), lv) + 1j * haar_analysis(jnp.imag(img), lv)
        return rnd(co.reshape(-1, r * r).astype(jnp.complex64))

    return refniht.niht(mv, rmv, rnd(Yhat), r * r, **kw)


class Cell:
    """One configuration under one traffic mix, on a ``batch`` mesh."""

    def __init__(self, cfg, traffic, keys, mesh, spans):
        from repro.core.operators import (ComposedOperator, SubsampledFourierOperator,
                                          WaveletSynthesisOperator)
        from repro.parallel.batch import BatchServer
        from repro.sensing import quantize_observations

        self.cfg, self.keys, self.spans = cfg, keys, spans
        self.rows = traffic["chunk_rows"]
        self.n_pool = traffic["pool_chunks"]
        self._quantize = quantize_observations
        sol = cfg["solver"]
        t0 = time.monotonic()
        m = mask(cfg)
        self.idx = jnp.asarray(np.flatnonzero(m.ravel()), jnp.int32)
        self.op = ComposedOperator(SubsampledFourierOperator.from_mask(m),
                                   WaveletSynthesisOperator(cfg["resolution"],
                                                            cfg["wavelet"]))
        self.pool = jax.block_until_ready(
            make_rows(keys["pool"], np.arange(self.n_pool * self.rows), self.idx, cfg,
                      self.n_pool))
        t1 = time.monotonic()
        self.server = BatchServer(
            self.op, cfg["n_sparse"], sol["n_iters"], mesh=mesh, key=keys["server"],
            threshold=sol["threshold"], c=sol["c"], shrink_k=sol["shrink_k"],
            max_backtracks=sol["max_backtracks"], real_signal=sol["real_signal"],
            nonneg=sol["nonneg"], with_trace=False, early_exit=sol["early_exit"],
            exit_tol=sol["exit_tol"])
        self.times = {"mask and pool": t1 - t0, "server": time.monotonic() - t1}

    def request(self, i, key):
        """Chunk ``i``: quantize y per band, solve, answer on the host."""
        cfg = self.cfg
        with self.spans("bench.quantize_y"):
            Yq = self._quantize(self.pool[i % self.n_pool], cfg["bits_y"], key,
                                granularity="per_band", op=self.op,
                                n_bands=cfg["n_bands"])
        with self.spans("bench.submit"):
            res = self.server.submit(Yq, key=key)
        with self.spans("bench.fetch"):
            x = np.asarray(res.x)
        return x, (res.trace.mu, res.trace.support_changed, res.trace.backtracks)

    def drop_pool(self):
        self.pool = None

    def free(self):
        self.pool = None
        self.server = None

    def reference(self, chunks, lowp=False):
        """Plain f32 NIHT of each (chunk index, observation key) in ``chunks``
        (bfloat16 data throughout with ``lowp``: the control). Rows in chunk
        order, as one (len(chunks) * rows, N) numpy array."""
        cfg, sol, r = self.cfg, self.cfg["solver"], self.cfg["resolution"]
        band = bands(self.idx, r, cfg["n_bands"])
        rows = np.concatenate([np.arange((i % self.n_pool) * self.rows,
                                         (i % self.n_pool + 1) * self.rows)
                               for i, _ in chunks])
        Y = make_rows(self.keys["pool"], rows, self.idx, cfg)[0]
        Yhat = jnp.concatenate([
            quantize_per_band(Y[j * self.rows:(j + 1) * self.rows], cfg["bits_y"], k,
                              band, cfg["n_bands"])
            for j, (_, k) in enumerate(chunks)])
        out = _solve(Yhat, self.idx, r=r, lowp=lowp, s=cfg["n_sparse"],
                     n_iters=sol["n_iters"], c=sol["c"], shrink_k=sol["shrink_k"],
                     max_backtracks=sol["max_backtracks"],
                     real_signal=sol["real_signal"], nonneg=sol["nonneg"])
        return np.asarray(out)
