"""Plain f32 normalized iterative hard thresholding: the benchmark's reference.

Written from the paper's Algorithm 1 (fixed quantization, one operator for
both products), in straightforward ``jax.numpy``; it imports nothing of the
system under test. The operator is any pair of functions ``mv`` (B, N) ->
(B, M) and ``rmv`` (B, M) -> (B, N); each row is its own problem:

* residual R = y - Phi x, gradient G = Phi^H R;
* support Gamma = supp(x), or the s largest |G| while x = 0;
* step mu = ||G_Gamma||^2 / ||Phi G_Gamma||^2;
* proposal x+ = H_s(P(x + mu G)), P the projection onto real (nonnegative)
  images when the signal is real;
* accept when supp(x+) = Gamma or mu <= (1 - c) ||x+ - x||^2 / ||Phi (x+ - x)||^2,
  else mu <- mu / (k (1 - c)) and propose again, at most ``max_backtracks``
  times.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-30


def sq(v):
    """Per-row squared l2 norm, real or complex."""
    return jnp.sum(jnp.real(v) ** 2 + jnp.imag(v) ** 2, axis=-1)


def top_s(v, s):
    """Boolean mask of the s largest |v| of each row."""
    _, idx = jax.lax.top_k(jnp.abs(v), s)
    rows = jnp.arange(v.shape[0])[:, None]
    return jnp.zeros(v.shape, bool).at[rows, idx].set(True)


def niht(mv, rmv, Y, n, *, s, n_iters, c, shrink_k, max_backtracks,
         real_signal, nonneg):
    """Recover every row of ``Y`` (B, M); returns X (B, n) float32 for a real
    signal, complex64 otherwise."""
    b = Y.shape[0]
    x_dtype = jnp.float32 if real_signal else jnp.complex64

    def project(a):
        if real_signal:
            a = jnp.real(a)
            if nonneg:
                a = jnp.maximum(a, 0.0)
        return a.astype(x_dtype)

    def h_s(a):
        return jnp.where(top_s(a, s), a, jnp.zeros_like(a))

    def step(_, X):
        G = rmv(Y - mv(X))
        gamma = jnp.where((sq(X) == 0.0)[:, None], top_s(G, s), X != 0)
        Gg = jnp.where(gamma, G, 0)
        mu0 = sq(Gg) / (sq(mv(Gg)) + EPS)

        def propose(mu):
            return h_s(project(X + mu[:, None] * G))

        def accepted(mu, Xp):
            same = jnp.all((Xp != 0) == gamma, axis=-1)
            D = Xp - X
            return same | (mu <= (1.0 - c) * (sq(D) / (sq(mv(D)) + EPS)))

        def cond(state):
            _, _, tries, todo = state
            return jnp.any(todo & (tries < max_backtracks))

        def body(state):
            mu, Xp, tries, todo = state
            live = todo & (tries < max_backtracks)
            mu = jnp.where(live, mu / (shrink_k * (1.0 - c)), mu)
            Xp = jnp.where(live[:, None], propose(mu), Xp)
            return mu, Xp, tries + live, live & ~accepted(mu, Xp)

        X0 = propose(mu0)
        state = (mu0, X0, jnp.zeros(b, jnp.int32), ~accepted(mu0, X0))
        return jax.lax.while_loop(cond, body, state)[1]

    return jax.lax.fori_loop(0, n_iters, step, jnp.zeros((b, n), x_dtype))
