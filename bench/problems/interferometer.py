"""Radio-interferometric sky recovery: one station, one subband operator.

The station's measurement operator, the skies and their visibilities are the
benchmark's own generators (after the paper's supplementary section 7):

* L antennas uniform in a disc, laid out by a fixed numpy seed (a station
  does not move); every ordered pair of distinct antennas is a baseline,
  u = (p_i - p_k) / lambda, so M = L (L - 1);
* an r x r grid of direction cosines over [-d, d]^2, N = r^2;
* Phi[z, w] = exp(-2 pi j <u_z, l_w>), complex64;
* a sky is ``n_sources`` point sources of flux U(lo, hi) on distinct cells of
  a ``min_sep``-coarsened grid, jittered inside their cell;
* y = Phi x + e, e circular complex Gaussian at ``snr_db`` per row.

The system under test gets Phi once, at server construction, and packs it;
the benchmark keeps no reference to the dense Phi after that. The reference
makes Phi again after the window and quantizes it with its own copy of the
quantizer (``quantize_complex``), so that it streams the same codes as the
program without taking anything the program made.
"""
from __future__ import annotations

import math
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import refniht

C_LIGHT = 299_792_458.0
HI = jax.lax.Precision.HIGHEST


def geometry(cfg):
    """(uv (M, 2) in wavelengths, grid (N, 2)) as float32 numpy arrays."""
    st = cfg["station"]
    rng = np.random.default_rng(st["layout_seed"])
    n_ant = st["n_antennas"]
    rad = st["field_radius_m"] * np.sqrt(rng.uniform(size=n_ant))
    th = rng.uniform(0, 2 * np.pi, size=n_ant)
    pos = np.stack([rad * np.cos(th), rad * np.sin(th)], axis=1)
    d = ((pos[:, None, :] - pos[None, :, :]) / (C_LIGHT / st["freq_hz"])).reshape(-1, 2)
    uv = d[~np.eye(n_ant, dtype=bool).ravel()]
    lin = np.linspace(-cfg["extent"], cfg["extent"], cfg["resolution"])
    ll, mm = np.meshgrid(lin, lin, indexing="ij")
    grid = np.stack([ll.ravel(), mm.ravel()], axis=1)
    return uv.astype(np.float32), grid.astype(np.float32)


@jax.jit
def make_phi(uv, grid):
    phase = -2.0 * jnp.pi * (uv[:, :1] * grid[:, 0] + uv[:, 1:] * grid[:, 1])
    return jnp.exp(1j * phase).astype(jnp.complex64)


def _sky(key, cfg):
    r, ns, sep, margin = (cfg["resolution"], cfg["n_sources"], cfg["min_sep"],
                          cfg["margin"])
    kpos, kflux, kjit = jax.random.split(key, 3)
    cells = (r - 2 * margin) // sep
    _, flat = jax.lax.top_k(jax.random.uniform(kpos, (cells * cells,)), ns)
    jit = jax.random.randint(kjit, (2, ns), 0, max(1, sep - 1))
    ii = jnp.clip((flat // cells) * sep + margin + jit[0], 0, r - 1)
    jj = jnp.clip((flat % cells) * sep + margin + jit[1], 0, r - 1)
    lo, hi = cfg["flux_range"]
    flux = jax.random.uniform(kflux, (ns,), minval=lo, maxval=hi)
    return jnp.zeros((r, r), jnp.float32).at[ii, jj].set(flux).ravel()


def _observe(phi, X, keys, snr_db):
    """Y = Phi X + e, one row per sky, noise at ``snr_db`` per row."""
    re = jnp.matmul(X, jnp.real(phi).T, precision=HI)
    im = jnp.matmul(X, jnp.imag(phi).T, precision=HI)
    clean = jax.lax.complex(re, im)
    m = clean.shape[-1]
    sigma = jnp.sqrt(refniht.sq(clean) / 10.0 ** (snr_db / 10.0) / m / 2.0)

    def noise(k):
        kr, ki = jax.random.split(k)
        return jax.lax.complex(jax.random.normal(kr, (m,)), jax.random.normal(ki, (m,)))

    return clean + sigma[:, None] * jax.vmap(noise)(keys)


SKY_KEYS = ("resolution", "n_sources", "min_sep", "margin", "flux_range", "snr_db")


def sky_args(cfg):
    return {k: tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k] for k in SKY_KEYS}


@partial(jax.jit, static_argnames=("n_out",) + SKY_KEYS)
def _make_rows(phi, pool_key, rows, *, n_out, snr_db, **sky):
    keys = jax.vmap(lambda r: jax.random.fold_in(pool_key, r))(rows)
    both = jax.vmap(jax.random.split)(keys)
    X = jax.vmap(lambda k: _sky(k, sky))(both[:, 0])
    Y = _observe(phi, X, both[:, 1], snr_db)
    return tuple(jnp.split(Y, n_out))


def make_rows(phi, pool_key, rows, cfg, n_out=1):
    """Observations of the skies with global row indices ``rows``, in
    ``n_out`` equal chunks, each its own device array."""
    return _make_rows(phi, pool_key, jnp.asarray(rows, jnp.int32), n_out=n_out,
                      **sky_args(cfg))


def quantize_real(v, bits, key, scale):
    """Stochastic rounding of v / scale onto the odd grid [-K, K], K = 2^(b-2):
    codes as float32 (the paper's Q_b, section 3)."""
    k = 2 ** (bits - 2)
    scaled = jnp.clip(v / scale, -1.0, 1.0) * k
    low = jnp.floor(scaled)
    u = jax.random.uniform(key, v.shape, dtype=jnp.float32)
    return jnp.clip(low + (u < scaled - low).astype(jnp.float32), -k, k)


def _max_abs(v):
    m = jnp.max(jnp.abs(v))
    return jnp.where(m > 0, m, 1.0)


def quantize_complex(v, bits, key):
    """Q_b of a complex tensor with one scale: (real part, imaginary part),
    dequantized, as float32."""
    re, im = jnp.real(v), jnp.imag(v)
    scale = jnp.maximum(_max_abs(re), _max_abs(im))
    kre, kim = jax.random.split(key)
    step = scale / 2 ** (bits - 2)
    return (quantize_real(re, bits, kre, scale) * step,
            quantize_real(im, bits, kim, scale) * step)


class Cell:
    """One configuration under one traffic mix, on a ``batch`` mesh."""

    def __init__(self, cfg, traffic, keys, mesh, spans):
        from repro.parallel.batch import BatchServer

        self.cfg, self.keys, self.spans = cfg, keys, spans
        self.rows = traffic["chunk_rows"]
        self.n_pool = traffic["pool_chunks"]
        sol = cfg["solver"]
        t0 = time.monotonic()
        uv, grid = geometry(cfg)
        phi = make_phi(uv, grid)
        self.pool = jax.block_until_ready(
            make_rows(phi, keys["pool"], np.arange(self.n_pool * self.rows), cfg,
                      self.n_pool))
        t1 = time.monotonic()
        self.server = BatchServer(
            phi, cfg["n_sources"], sol["n_iters"], mesh=mesh,
            bits_phi=cfg["bits_phi"], bits_y=cfg["bits_y"], key=keys["server"],
            requantize="fixed", backend="packed", threshold=sol["threshold"],
            c=sol["c"], shrink_k=sol["shrink_k"],
            max_backtracks=sol["max_backtracks"], real_signal=sol["real_signal"],
            nonneg=sol["nonneg"], with_trace=False,
            scale_granularity=cfg["scale_granularity"],
            early_exit=sol["early_exit"], exit_tol=sol["exit_tol"])
        del phi
        jax.block_until_ready(self.server.phi)
        self.times = {"operator and pool": t1 - t0, "server (pack)": time.monotonic() - t1}

    def request(self, i, key):
        """Chunk ``i`` through the server; the answer on the host, and the
        solver's per-iteration counts (left on the device)."""
        with self.spans("bench.submit"):
            res = self.server.submit(self.pool[i % self.n_pool], key=key)
        with self.spans("bench.fetch"):
            x = np.asarray(res.x)
        return x, (res.trace.mu, res.trace.support_changed, res.trace.backtracks)

    def free(self):
        """Drop the harness's pool and the server (with its packed operator)."""
        self.pool = None
        self.server = None

    def drop_pool(self):
        self.pool = None

    def reference(self, chunks, bits_phi=None, precision="highest"):
        """Plain f32 NIHT of each (chunk index, observation key) in ``chunks``,
        on this configuration's codes at full float32 precision. A control
        lowers one of the two: ``bits_phi`` codes, or the products at a lower
        ``precision`` ("high": three bfloat16 passes; "default": one). Rows
        in chunk order, as one (len(chunks) * rows, N) numpy array."""
        cfg, sol = self.cfg, self.cfg["solver"]
        bits = bits_phi or cfg["bits_phi"]
        uv, grid = geometry(cfg)
        phi = make_phi(uv, grid)
        rows = np.concatenate([np.arange((i % self.n_pool) * self.rows,
                                         (i % self.n_pool + 1) * self.rows)
                               for i, _ in chunks])
        Y = make_rows(phi, self.keys["pool"], rows, cfg)[0]
        # the server's pack key, as the deployment states it: fold_in(k, 0)
        # of the second half of the construction key
        kq = jax.random.fold_in(jax.random.split(self.keys["server"])[1], 0)
        A, B = quantize_complex(phi, bits, kq)
        del phi
        # in-solve observation quantization: every row folds the first half
        # of its chunk's key
        Yhat = jnp.concatenate([
            jax.vmap(lambda y, k=k: jax.lax.complex(
                *quantize_complex(y, cfg["bits_y"], jax.random.split(k)[0])))(
                Y[j * self.rows:(j + 1) * self.rows])
            for j, (_, k) in enumerate(chunks)])
        out = _solve(A, B, Yhat, s=cfg["n_sources"], n_iters=sol["n_iters"],
                     c=sol["c"], shrink_k=sol["shrink_k"],
                     max_backtracks=sol["max_backtracks"],
                     real_signal=sol["real_signal"], nonneg=sol["nonneg"],
                     precision=precision)
        return np.asarray(out)


def _bf16(v):
    return v.astype(jnp.bfloat16).astype(jnp.float32)


def matmul(a, b, precision="highest"):
    """a @ b in float32 at ``precision``. "highest" is full float32; "high"
    and "default" are spelled out as the TPU computes them, so that they read
    the same on any platform: the operands split into bfloat16 parts, a = a1
    + a2, and the products a1 b1 + a1 b2 + a2 b1 ("high", three passes) or
    a1 b1 alone ("default", one pass), each exact, summed in float32."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=HI)
    a1, b1 = _bf16(a), _bf16(b)
    out = jnp.matmul(a1, b1, precision=HI)
    if precision == "high":
        out += (jnp.matmul(a1, _bf16(b - b1), precision=HI)
                + jnp.matmul(_bf16(a - a1), b1, precision=HI))
    return out


def _mv(A, B, X, p):
    """(A + jB) X for real or complex X (B, N) -> (B, M)."""
    xr, xi = jnp.real(X), jnp.imag(X)
    rr = matmul(xr, A.T, p)
    ir = matmul(xr, B.T, p)
    if not jnp.iscomplexobj(X):
        return jax.lax.complex(rr, ir)
    ri = matmul(xi, A.T, p)
    ii = matmul(xi, B.T, p)
    return jax.lax.complex(rr - ii, ri + ir)


def _rmv(A, B, R, p):
    """(A + jB)^H R, (B, M) -> (B, N)."""
    rr, ri = jnp.real(R), jnp.imag(R)
    re = matmul(rr, A, p) + matmul(ri, B, p)
    im = matmul(ri, A, p) - matmul(rr, B, p)
    return jax.lax.complex(re, im)


@partial(jax.jit, static_argnames=("s", "n_iters", "c", "shrink_k",
                                   "max_backtracks", "real_signal", "nonneg",
                                   "precision"))
def _solve(A, B, Yhat, *, precision, **kw):
    return refniht.niht(lambda X: _mv(A, B, X, precision),
                        lambda R: _rmv(A, B, R, precision), Yhat, A.shape[1], **kw)


def operator_work(cfg, rows):
    """Least work of ONE application of the operator to ``rows`` vectors, as
    the configuration states it: the codes of one orientation at
    ``bits_phi`` (real and imaginary planes) and their one scale, the input
    and the output; two real products of an (M, N) plane with ``rows``
    vectors. Returns (bytes, flops)."""
    m = cfg["station"]["n_antennas"] * (cfg["station"]["n_antennas"] - 1)
    n = cfg["resolution"] ** 2
    codes = 2 * math.ceil(m * n * cfg["bits_phi"] / 8)
    io = rows * (4 * n + 8 * m)
    return codes + 4 + io, 2 * 2 * m * n * rows

