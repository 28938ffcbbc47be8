"""Normalized Iterative Hard Thresholding — full precision and quantized (QNIHT).

Implements the paper's Algorithm 1 faithfully:

* adaptive step size  ``µ = ||g_Γ||² / ||Φ̂ g_Γ||²``  on the current support Γ,
* proposal ``x⁺ = H_s(x + µ g)`` with ``g = Φ̂₁†(ŷ − Φ̂₂ x)``,
* if the support changes, accept only when ``µ ≤ (1−c)·ω`` with
  ``ω = ||x⁺−x||² / ||Φ̂₁(x⁺−x)||²``; otherwise shrink ``µ ← µ/(k(1−c))`` and
  re-propose (``lax.while_loop`` backtracking),
* fresh unbiased stochastic quantizations ``Φ̂_{2n-1}, Φ̂_{2n}`` per iteration
  (``requantize="pair"``) or a single fixed quantization (``requantize="fixed"`` —
  what the CPU/FPGA systems actually stream, since data arrives pre-quantized).

The loop only touches Φ̂ through ``mv``/``rmv`` products, so it is generic over
the :mod:`repro.core.operators` backends:

* ``backend="dense"`` — dense XLA dots. With ``bits_phi`` set this is
  *fake quantization*: Φ̂'s values are quantized but carried as f32/c64, so the
  math matches deployment while the memory traffic stays full-precision.
  Faithful to Algorithm 1 in both ``requantize`` modes.
* ``backend="packed"`` — ``requantize="fixed"`` only: Φ̂ and Φ̂† are quantized
  ONCE (shared codes, identical to the dense fixed path bit-for-bit) and packed
  to uint8; every iteration streams the packed codes through the Pallas ``qmm``
  kernels — 4/8/16× fewer operator bytes at 8/4/2 bits, the paper's headline
  systems result (Fig. 5/6, suppl. §8.1).
* **matrix-free** — pass an *operator* (anything with ``mv``/``rmv``/``shape``/
  ``dtype``, e.g. ``SubsampledFourierOperator``) instead of the dense array;
  the loop never materializes Φ. This is how the MRI workload (§5) runs at
  sizes where a dense partial-Fourier Φ would be gigabytes. ``bits_y`` still
  quantizes the observations; ``bits_phi``/``backend`` stay at their defaults.

``qniht_batch`` recovers B observation vectors of the SAME Φ̂ at once: every
matvec lifts to one (B, ·) matmul / kernel call, amortizing the Φ̂ stream
across the batch (the heavy-traffic serving scenario). Key contract: row ``b``
of ``qniht_batch(phi, Y, key=k)`` computes exactly what ``qniht(phi, Y[b],
key=k)`` computes (same quantization draws), up to f32 batching accumulation.

``qniht_batch_sharded`` splits that batch over a 1-D ``batch`` device mesh
(:mod:`repro.parallel.batch`): Y and all per-item solver state sharded, the
packed operator codes/scales replicated, every item bit-identical to the
single-device path. Combined with ``early_exit`` (skip iterations once a
shard's rows all hit a bitwise fixed point) this is the heavy-traffic serving
mode — see ``docs/architecture.md``.

``threshold="hsthresh"`` (real-signal path) swaps the exact ``top_k`` H_s for
the streaming histogram-select-mask kernel (paper §8's FPGA top-S search);
support size stays ≤ s by construction.

One ``lax.while_loop`` over iterations (:func:`_segment_core`) serves every
entry point — one-shot, sharded, segmented and checkpointed, the continuous
scheduler — each compiled into one program that returns the trace.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.operators import is_linear_operator, make_iteration_operators
from repro.core.threshold import hard_threshold, top_s_mask
from repro.kernels.hsthresh.ops import hsthresh
from repro.quant.formats import as_granularity
from repro.quant.quantize import fake_quantize


class IHTTrace(NamedTuple):
    """Per-iteration diagnostics (length n_iters; batched runs add a B axis)."""

    resid_q: jax.Array        # ||ŷ − Φ̂ x||₂ (the cost the algorithm minimizes)
    resid_true: jax.Array     # ||y − Φ x||₂ against full-precision data
    mu: jax.Array             # accepted step size
    support_changed: jax.Array
    backtracks: jax.Array


class IHTResult(NamedTuple):
    """``x`` and the per-iteration ``trace``. ``iters_run`` (int32, one per
    row; a scalar from :func:`qniht`) counts the iterations the loop executed
    for the shard that solved the row: ``n_iters`` without early exit, the
    trip count of the early-exit loop with it. It stays on the device until a
    caller reads it."""

    x: jax.Array
    trace: IHTTrace
    iters_run: Optional[jax.Array] = None


# consecutive sub-tol updates required before the early-exit freeze rule
# (exit_tol > 0) declares a row stalled — see _segment_core
_EXIT_PATIENCE = 3


def _sqnorm(v: jax.Array) -> jax.Array:
    return jnp.real(jnp.vdot(v, v))


def _rows_sqnorm(v: jax.Array) -> jax.Array:
    """Squared l2 norm along the last axis (per problem)."""
    return jnp.real(jnp.sum(v * jnp.conj(v), axis=-1))


def _project(a: jax.Array, real_signal: bool, nonneg: bool) -> jax.Array:
    if real_signal:
        a = jnp.real(a)
        if nonneg:
            a = jnp.maximum(a, 0.0)
    return a


def _make_hs(threshold: str, s: int):
    """Batched H_s: (B, N) → (B, N) with per-row support size ≤ s."""
    if threshold == "topk":
        return jax.vmap(lambda v: hard_threshold(v, s))
    if threshold == "hsthresh":
        return jax.vmap(lambda v: hsthresh(v, s))
    raise ValueError(f"unknown threshold {threshold!r} (use 'topk' or 'hsthresh')")


def _niht_iteration_batch(
    X: jax.Array,
    Yhat: jax.Array,
    op1,
    op2,
    s: int,
    c: float,
    shrink_k: float,
    max_backtracks: int,
    real_signal: bool,
    nonneg: bool,
    hs,
):
    """One NIHT step (Algorithm 1 body) on a batch of problems sharing Φ̂.

    ``op1`` is Φ̂_{2n-1} (gradient / step-size / acceptance), ``op2`` is Φ̂_{2n}
    (residual), matching the paper's pairing. Every operator application serves
    the whole batch in one matmul; support logic and backtracking are per-row
    (a row stops shrinking µ as soon as its own acceptance test passes).
    Returns (X_new, mu, changed, n_backtracks), all leading-axis B.
    """
    eps = jnp.asarray(1e-30, jnp.float32)
    # named scopes (mv, rmv, threshold, backtrack) only label the lowered
    # ops' metadata, so a profiler trace can attribute device time to them
    with jax.named_scope("mv"):
        PX = op2.mv(X)
    R = Yhat - PX
    with jax.named_scope("rmv"):
        G = op1.rmv(R)

    # Γ: support of x, or (first iteration, x = 0) the top-s of the first gradient.
    on_init = _rows_sqnorm(X) == 0.0
    mask_x = jnp.abs(X) > 0
    with jax.named_scope("threshold"):
        mask_g = jax.vmap(lambda g: top_s_mask(g, s))(G)
    gamma = jnp.where(on_init[:, None], mask_g, mask_x)

    Gg = jnp.where(gamma, G, jnp.zeros_like(G))
    with jax.named_scope("mv"):
        PGg = op1.mv(Gg)
    mu0 = _rows_sqnorm(Gg) / (_rows_sqnorm(PGg) + eps)

    def propose(mu):
        A = X.astype(G.dtype) + mu[:, None] * G
        A = _project(A, real_signal, nonneg).astype(X.dtype)
        with jax.named_scope("threshold"):
            return hs(A)

    def accept(mu, Xp):
        new_mask = jnp.abs(Xp) > 0
        same = jnp.all(new_mask == gamma, axis=-1)
        D = Xp - X
        with jax.named_scope("mv"):
            PD = op1.mv(D)
        omega = _rows_sqnorm(D) / (_rows_sqnorm(PD) + eps)
        return same | (mu <= (1.0 - c) * omega)

    X0 = propose(mu0)
    active0 = ~accept(mu0, X0)
    nbt0 = jnp.zeros(X.shape[0], jnp.int32)

    def cond(carry):
        _, _, nbt, active = carry
        return jnp.any(active & (nbt < max_backtracks))

    def body(carry):
        mu, Xp, nbt, active = carry
        act = active & (nbt < max_backtracks)
        mu_new = jnp.where(act, mu / (shrink_k * (1.0 - c)), mu)
        Xp_new = jnp.where(act[:, None], propose(mu_new), Xp)
        nbt_new = nbt + act.astype(jnp.int32)
        still_rejected = act & ~accept(mu_new, Xp_new)
        return mu_new, Xp_new, nbt_new, still_rejected

    with jax.named_scope("backtrack"):
        mu, X_new, n_bt, _ = jax.lax.while_loop(cond, body, (mu0, X0, nbt0, active0))
    changed = ~jnp.all((jnp.abs(X_new) > 0) == gamma, axis=-1)
    return X_new, mu, changed, n_bt


def niht_iteration(
    x: jax.Array,
    y_hat: jax.Array,
    op1,
    op2,
    s: int,
    c: float,
    shrink_k: float,
    max_backtracks: int,
    real_signal: bool,
    nonneg: bool,
    threshold: str = "topk",
):
    """One NIHT step on a single problem. ``op1``/``op2`` follow the
    :mod:`repro.core.operators` protocol (``mv``/``rmv`` accepting a batch
    axis); see :func:`_niht_iteration_batch` for the paper's pairing.
    Returns (x_new, mu, changed, n_backtracks)."""
    X, mu, ch, nbt = _niht_iteration_batch(
        x[None, :], y_hat[None, :], op1, op2, s, c, shrink_k, max_backtracks,
        real_signal, nonneg, _make_hs(threshold, s),
    )
    return X[0], mu[0], ch[0], nbt[0]


def _validate(phi, bits_phi, bits_y, key, requantize, backend, threshold, real_signal,
              scale_granularity="per_tensor", group_size=None, early_exit=False,
              exit_tol=0.0):
    if (bits_phi or bits_y) and key is None:
        raise ValueError("quantized NIHT needs a PRNG key")
    if requantize not in ("pair", "fixed"):
        raise ValueError(f"unknown requantize {requantize!r}")
    if early_exit and bits_phi and requantize == "pair":
        raise ValueError(
            "early_exit skips iterations once x reaches a bitwise fixed point, "
            "which is only absorbing when every iteration applies the SAME "
            "operators; requantize='pair' redraws Φ̂ each iteration — use "
            "requantize='fixed' (or full precision) with early_exit")
    if exit_tol < 0.0:
        raise ValueError(f"exit_tol must be >= 0, got {exit_tol}")
    if exit_tol > 0.0 and not early_exit:
        raise ValueError("exit_tol is the early_exit freeze tolerance; set early_exit=True")
    if backend not in ("dense", "packed"):
        raise ValueError(f"unknown backend {backend!r} (use 'dense' or 'packed')")
    gran = as_granularity(scale_granularity, group_size)  # validates the spelling
    if not gran.is_per_tensor and backend != "packed":
        raise ValueError(
            "scale_granularity selects the Φ̂ scale layout of the packed "
            "streaming backend; use backend='packed' (for per-band observation "
            "scaling quantize y up front — see repro.sensing.quantize_observations)")
    if is_linear_operator(phi):
        if bits_phi:
            raise ValueError(
                "bits_phi only applies to dense Φ arrays; a matrix-free operator "
                "owns its representation (quantize inside the operator instead)")
        if backend != "dense":
            raise ValueError(
                "backend='packed' packs a dense Φ array; matrix-free operators "
                "are already their own streaming representation")
    if backend == "packed":
        if not bits_phi:
            raise ValueError("backend='packed' needs bits_phi (it streams packed codes)")
        if requantize != "fixed":
            raise ValueError(
                "backend='packed' is the requantize='fixed' deployment mode; "
                "re-packing fresh codes per iteration would stream MORE bytes "
                "than it saves — use backend='dense' for requantize='pair'")
    if threshold == "hsthresh" and not real_signal:
        raise ValueError("threshold='hsthresh' is the real-signal streaming H_s")


def _solver_setup(
    phi, Y, s, bits_phi, bits_y, key, requantize, backend, threshold,
    c, shrink_k, max_backtracks, real_signal, nonneg, with_trace,
    scale_granularity, group_size,
):
    """Prologue of the solver loop: returns ``iteration(X, i)``, one
    Algorithm 1 step at global iteration index ``i``. Everything stochastic —
    the ŷ draw and the per-iteration Φ̂ pair factory — is derived
    deterministically from ``key``, and ``iteration`` consumes the *global*
    index, so running the range [0, n) in one loop or in segments produces
    bit-identical iterates.
    """
    key = key if key is not None else jax.random.PRNGKey(0)
    ky, kphi = jax.random.split(key)

    # One stochastic draw ŷ per problem, all rows folding the same ky so that
    # batch row b reproduces the single-problem run with the same key.
    Yhat = jax.vmap(lambda yy: fake_quantize(yy, bits_y, ky))(Y) if bits_y else Y

    hs = _make_hs(threshold, s)
    phi_true, get_ops = make_iteration_operators(
        phi, bits_phi, requantize, backend, kphi,
        granularity=as_granularity(scale_granularity, group_size))

    def iteration(X, i):
        op1, op2 = get_ops(i)
        X_new, mu, changed, n_bt = _niht_iteration_batch(
            X, Yhat, op1, op2, s, c, shrink_k, max_backtracks,
            real_signal, nonneg, hs,
        )
        if with_trace:
            with jax.named_scope("mv"):
                PX, TX = op2.mv(X_new), phi_true.mv(X_new)
            rq = jnp.sqrt(_rows_sqnorm(Yhat - PX))
            rt = jnp.sqrt(_rows_sqnorm(Y - TX))
        else:
            # skip the residual matvecs (one of them streams dense f32 Φ —
            # benchmarks disable the trace so the loop is pure algorithm traffic)
            # np-built so the intentional NaN marker is a constant,
            # not an op that trips jax_debug_nans (see analysis.sanitize)
            rq = rt = jnp.asarray(np.full(X.shape[0], np.nan, np.float32))
        return X_new, (rq, rt, mu, changed, n_bt)

    return iteration


class SolverState(NamedTuple):
    """Complete solver state at an iteration boundary — the checkpoint unit.

    A registered pytree (NamedTuple of arrays) holding everything the
    iteration map consumes, so ``solver_segment`` can stop after any iteration
    and a later process can resume **bit-identically** — the acceptance bar of
    the preemption-safe recovery path (:mod:`repro.launch.resilience`):

    * ``k``       — () int32, the next iteration index (segments resume here).
    * ``X``       — (B, N) iterate. The support Γ is implicit: ``|X| > 0``
      (plus the top-s-of-gradient rule at ``X == 0``), exactly as the
      iteration body derives it.
    * ``done``    — (B,) per-row convergence flags (the ``early_exit`` state).
    * ``streak``  — (B,) consecutive sub-``exit_tol`` update counters (the
      freeze rule's patience state; all-zero when ``exit_tol == 0``).
    * ``last``    — the last emitted per-row trace row (µ, backtrack counts,
      residuals): what frozen rows re-emit and the stationary tail-fill uses.
    * ``trace``   — (n_iters, B) per-iteration buffers, written for
      iterations ``< k``.
    * ``Y``       — (B, M) raw observations. ŷ and the Φ̂ draws are
      *recomputed* from (``Y``, ``key``) each segment rather than stored —
      they are deterministic functions of both, which keeps the checkpoint
      minimal and the bit-identity contract trivially segmentation-invariant.
    * ``key``     — the run's PRNG key, replicated.

    Every per-row leaf has the batch axis leading (``trace`` second), so the
    sharded path splits the whole state by rows with one spec tree, and a
    checkpoint written at one mesh width restores onto any other (elastic
    resume — pad rows are bitwise fixed points, see
    :func:`repro.parallel.batch.pad_state`).
    """

    k: jax.Array
    X: jax.Array
    done: jax.Array
    streak: jax.Array
    last: IHTTrace
    trace: IHTTrace
    Y: jax.Array
    key: jax.Array


def solver_init(
    phi, Y: jax.Array, s: int, n_iters: int = 50, *,
    bits_phi: Optional[int] = None, bits_y: Optional[int] = None,
    key: Optional[jax.Array] = None, requantize: str = "pair",
    backend: str = "dense", threshold: str = "topk", c: float = 0.01,
    shrink_k: float = 2.0, max_backtracks: int = 30, real_signal: bool = False,
    nonneg: bool = False, with_trace: bool = True,
    scale_granularity: str = "per_tensor", group_size: Optional[int] = None,
    early_exit: bool = False, exit_tol: float = 0.0,
) -> SolverState:
    """Fresh :class:`SolverState` for ``qniht_batch(phi, Y, s, n_iters, ...)``
    run in segments. Same validation and defaults as :func:`qniht_batch`,
    whose own state this is. Composable under :func:`jax.eval_shape` — that
    is how the checkpoint restore target is built without touching data."""
    if Y.ndim != 2:
        raise ValueError("solver_init expects Y of shape (B, M); wrap one y as y[None]")
    _validate(phi, bits_phi, bits_y, key, requantize, backend, threshold,
              real_signal, scale_granularity, group_size, early_exit, exit_tol)
    B = Y.shape[0]
    x_dtype = jnp.float32 if real_signal else (
        phi.dtype if jnp.issubdtype(jnp.dtype(phi.dtype), jnp.complexfloating)
        else jnp.float32
    )
    # np-built NaN marker: a transfer, not an op, so eager solver_init
    # does not trip jax_debug_nans (repro.analysis.sanitize)
    nanrow = jnp.asarray(np.full(B, np.nan, np.float32))
    last = IHTTrace(resid_q=nanrow, resid_true=nanrow,
                    mu=jnp.zeros((B,), jnp.float32),
                    support_changed=jnp.zeros((B,), bool),
                    backtracks=jnp.zeros((B,), jnp.int32))
    return SolverState(
        k=jnp.zeros((), jnp.int32),
        X=jnp.zeros((B, phi.shape[1]), x_dtype),
        done=jnp.zeros((B,), bool),
        streak=jnp.zeros((B,), jnp.int32),
        last=last,
        trace=jax.tree_util.tree_map(
            lambda o: jnp.zeros((n_iters,) + o.shape, o.dtype), last),
        Y=Y,
        key=key if key is not None else jax.random.PRNGKey(0),
    )


# the solver configuration, static in every entry point's jit
_STATIC = (
    "s", "n_iters", "bits_phi", "bits_y", "requantize", "backend", "threshold",
    "c", "shrink_k", "max_backtracks", "real_signal", "nonneg", "with_trace",
    "scale_granularity", "group_size", "early_exit", "exit_tol",
)
# the loop's: n_iters lives in the trace buffer shape, a segment advances n_steps
_SEG_STATIC = tuple("n_steps" if a == "n_iters" else a for a in _STATIC)

# one source of truth for the solver-config defaults of the segmented entry
# points (solver_segment keyword defaults and the sharded/resilient drivers)
_SEG_DEFAULTS = dict(
    bits_phi=None, bits_y=None, requantize="pair", backend="dense",
    threshold="topk", c=0.01, shrink_k=2.0, max_backtracks=30,
    real_signal=False, nonneg=False, with_trace=True,
    scale_granularity="per_tensor", group_size=None, early_exit=False,
    exit_tol=0.0,
)


def _segment_core(
    phi, state: SolverState, *, n_steps, s, bits_phi, bits_y, requantize,
    backend, threshold, c, shrink_k, max_backtracks, real_signal, nonneg,
    with_trace, scale_granularity, group_size, early_exit, exit_tol,
) -> tuple[SolverState, jax.Array]:
    """The solver's one loop over iterations: advance ``state`` by up to
    ``n_steps`` iterations (fewer only at the horizon ``n_iters``). Returns
    the new state and, per row, the index ``k_stop`` the loop stopped at —
    from a fresh state, the loop's own trip count (the one-shot entry points'
    ``iters_run``). Every stochastic input is re-derived from (``Y``, ``key``)
    and the body consumes the global index ``k``, so segment boundaries are
    exact restart points: any split of ``[0, n_iters)`` — one segment for the
    one-shot entry points — gives bit-identical results.

    ``early_exit`` stops executing iteration bodies once EVERY row (of this
    shard) is done. A ``lax.while_loop``, NOT a scan of ``lax.cond``: under
    SPMD partitioning (``shard_map``) XLA rewrites a cond into a select that
    executes both branches, which would silently undo the skip; a loop's trip
    count cannot be select-ified, so a converged shard genuinely stops paying
    while other shards keep working (:mod:`repro.parallel.batch`). The
    skipped rows of the trace would all re-emit the stationary row, so they
    are filled instead of computed and ``k`` lands on ``min(k + n_steps,
    n_iters)`` on every shard: ``k`` stays replicated and the state
    independent of the mesh width it ran on (elastic resume). Two flavours:

    * ``exit_tol == 0.0`` (lossless): a row is done at a bitwise fixed point
      of the iteration map. With stationary operators (``requantize="fixed"``,
      packed, matrix-free, full precision) the map is a deterministic function
      of ``x``, so a fixed point is absorbing — a done row recomputes itself
      identically and the output is bit-for-bit that of ``early_exit=False``.
    * ``exit_tol > 0.0`` (freeze): a row is frozen — masked, its trace
      re-emitting its last live row — once ``‖x⁺−x‖ ≤ exit_tol·‖x⁺‖`` for
      ``_EXIT_PATIENCE`` consecutive iterations (one tiny step can be a
      backtracking artefact, not a stall). It catches rows orbiting tiny limit
      cycles that never hit an exact fixed point. A *heuristic* serving
      trade-off: a row on a long saddle plateau can freeze short of a late
      support escape, so it matches the full run only up to the quality its
      stall point reached (the scaling benchmark records both errors). The
      rule is deterministic and **row-local**, so results are bit-identical
      across ANY row grouping — single device, any mesh width.
    """
    n_iters = state.trace.mu.shape[0]
    iteration = _solver_setup(
        phi, state.Y, s, bits_phi, bits_y, state.key, requantize, backend,
        threshold, c, shrink_k, max_backtracks, real_signal, nonneg, with_trace,
        scale_granularity, group_size)
    k_end = jnp.minimum(state.k + n_steps, n_iters)

    def body(st):
        k, X, done, streak, last, bufs = st
        X_c, outs_c = iteration(X, k)
        if exit_tol == 0.0:
            # a done row recomputes itself identically (fixed point) — no
            # masking needed
            X_new, outs = X_c, outs_c
        else:
            X_new = jnp.where(done[:, None], X, X_c)
            outs = jax.tree_util.tree_map(
                lambda p, n_: jnp.where(done, p, n_), tuple(last), outs_c)
        bufs = jax.tree_util.tree_map(
            lambda buf, o: jax.lax.dynamic_update_index_in_dim(buf, o, k, 0),
            tuple(bufs), outs)
        if not early_exit:
            newly = jnp.zeros_like(done)
        elif exit_tol == 0.0:
            newly = jnp.all(X_new == X, axis=-1)
        else:
            small = _rows_sqnorm(X_new - X) <= (
                exit_tol * exit_tol) * _rows_sqnorm(X_new)
            streak = jnp.where(small, streak + 1, 0)
            newly = streak >= _EXIT_PATIENCE
        return k + 1, X_new, done | newly, streak, IHTTrace(*outs), IHTTrace(*bufs)

    def cond(st):
        k, _, done, _, _, _ = st
        live = k < k_end
        return live & ~jnp.all(done) if early_exit else live

    k_stop, X, done, streak, last, bufs = jax.lax.while_loop(
        cond, body,
        (state.k, state.X, state.done, state.streak, state.last, state.trace))
    if early_exit:
        # rows the early exit skipped would all re-emit the stationary row
        rows = jnp.arange(n_iters)[:, None]
        fill = (rows >= k_stop) & (rows < k_end)
        bufs = jax.tree_util.tree_map(
            lambda buf, o: jnp.where(fill, o[None, :], buf), bufs, last)
    return (SolverState(k=k_end, X=X, done=done, streak=streak, last=last,
                        trace=bufs, Y=state.Y, key=state.key),
            jnp.full(done.shape, k_stop, jnp.int32))


_segment_jit = partial(jax.jit, static_argnames=_SEG_STATIC)(_segment_core)


def solver_segment(
    phi, state: SolverState, n_steps: int, *, s: int,
    bits_phi: Optional[int] = None, bits_y: Optional[int] = None,
    requantize: str = "pair", backend: str = "dense", threshold: str = "topk",
    c: float = 0.01, shrink_k: float = 2.0, max_backtracks: int = 30,
    real_signal: bool = False, nonneg: bool = False, with_trace: bool = True,
    scale_granularity: str = "per_tensor", group_size: Optional[int] = None,
    early_exit: bool = False, exit_tol: float = 0.0,
) -> SolverState:
    """Run one segment of ``n_steps`` iterations (single-process path).

    Contract: for any split of ``[0, n_iters)`` into segments,
    ``solver_result`` of the final state is **bit-identical** to
    ``qniht_batch(phi, Y, ...)`` with the same arguments — the deterministic
    iteration map makes every segment boundary an exact restart point. The
    solver configuration must be passed identically to every call (it is
    static; :mod:`repro.launch.resilience` owns that bookkeeping and persists
    the state between segments through :mod:`repro.train.checkpoint`). The
    sharded equivalent is :func:`repro.parallel.batch.sharded_segment_run`.
    """
    if n_steps < 1:
        raise ValueError(f"n_steps must be >= 1, got {n_steps}")
    _validate(phi, bits_phi, bits_y, state.key, requantize, backend, threshold,
              real_signal, scale_granularity, group_size, early_exit, exit_tol)
    state, _ = _segment_jit(
        phi, state, n_steps=n_steps, s=s, bits_phi=bits_phi, bits_y=bits_y,
        requantize=requantize, backend=backend, threshold=threshold, c=c,
        shrink_k=shrink_k, max_backtracks=max_backtracks,
        real_signal=real_signal, nonneg=nonneg, with_trace=with_trace,
        scale_granularity=scale_granularity, group_size=group_size,
        early_exit=early_exit, exit_tol=exit_tol)
    return state


def solver_result(state: SolverState) -> IHTResult:
    """Wrap a :class:`SolverState` as the usual :class:`IHTResult`.

    Trace rows at iterations ``>= state.k`` (a run finalized before the
    horizon — e.g. a preempted partial result) are filled with the stationary
    last row, matching the early-exit tail-fill convention. ``iters_run`` is
    ``state.k`` for every row: the iterations the segments advanced, those an
    early exit filled included."""
    n_iters = state.trace.mu.shape[0]
    tail = jnp.arange(n_iters)[:, None] >= state.k
    trace = jax.tree_util.tree_map(
        lambda buf, o: jnp.where(tail, o[None, :], buf), state.trace, state.last)
    return IHTResult(x=state.X, trace=trace,
                     iters_run=jnp.full(state.X.shape[:1], state.k, jnp.int32))


def _qniht_core(phi, Y, key, *, n_iters, **cfg) -> IHTResult:
    """The one-shot solve of (B, M) ``Y``: a fresh state advanced through
    all ``n_iters`` in one segment, ``iters_run`` the loop's trip count."""
    state = solver_init(phi, Y, n_iters=n_iters, key=key, **cfg)
    state, iters = _segment_core(phi, state, n_steps=n_iters, **cfg)
    return solver_result(state)._replace(iters_run=iters)


@partial(jax.jit, static_argnames=_STATIC)
def qniht(
    phi: jax.Array,
    y: jax.Array,
    s: int,
    n_iters: int = 50,
    *,
    bits_phi: Optional[int] = None,
    bits_y: Optional[int] = None,
    key: Optional[jax.Array] = None,
    requantize: str = "pair",
    backend: str = "dense",
    threshold: str = "topk",
    c: float = 0.01,
    shrink_k: float = 2.0,
    max_backtracks: int = 30,
    real_signal: bool = False,
    nonneg: bool = False,
    with_trace: bool = True,
    scale_granularity: str = "per_tensor",
    group_size: Optional[int] = None,
    early_exit: bool = False,
    exit_tol: float = 0.0,
) -> IHTResult:
    """Low-precision NIHT (Algorithm 1). ``bits_phi=bits_y=None`` → plain NIHT.

    Args:
      phi: (M, N) measurement matrix (real or complex), or any matrix-free
        operator following the :mod:`repro.core.operators` protocol
        (``mv``/``rmv``/``shape``/``dtype``) — e.g.
        :class:`~repro.core.operators.SubsampledFourierOperator` for MRI, where
        a dense Φ would be gigabytes. Operator inputs require the default
        ``bits_phi=None``/``backend="dense"`` (the operator owns its own data
        representation); ``bits_y`` still quantizes the observations.
      y: (M,) observations.
      s: sparsity level.
      bits_phi / bits_y: data precision (2/4/8) or None for full precision.
      key: PRNG key for stochastic quantization (required when quantizing).
      requantize: "pair" (fresh Φ̂_{2n-1}, Φ̂_{2n} each iteration — Algorithm 1) or
        "fixed" (quantize once; what a deployed system streaming pre-quantized
        data does).
      backend: "dense" (fake-quantized f32 compute) or "packed" (stream packed
        uint8 codes through the Pallas qmm kernels; requires bits_phi and
        requantize="fixed" — same codes as the dense fixed path, 32/bits× fewer
        operator bytes per application). See the module docstring.
      threshold: "topk" (exact H_s) or "hsthresh" (streaming histogram H_s,
        real-signal path; support ≤ s).
      real_signal / nonneg: optional projections (sky images are real, >= 0).
      with_trace: compute per-iteration residual norms (costs one extra Φ̂ and
        one dense Φ matvec per iteration; disable for timing runs).
      scale_granularity / group_size: scale layout of the packed Φ̂ stream
        ("per_tensor" — the paper's single c_Φ, bit-identical to the historical
        behaviour; "per_channel"; "per_block" with ``group_size``). Group
        granularities quantize each orientation separately (packed backend
        only); see :mod:`repro.quant.formats` for layout and overhead.
      early_exit: skip remaining iteration bodies once x reaches a bitwise
        fixed point (stationary operators only — bit-identical output, see
        :func:`_segment_core`).
    """
    if y.ndim != 1:
        raise ValueError(
            f"qniht expects y of shape (M,), got ndim={y.ndim}; "
            "use qniht_batch for a (B, M) stack of observations")
    res = _qniht_core(
        phi, y[None, :], key, n_iters=n_iters, s=s, bits_phi=bits_phi,
        bits_y=bits_y, requantize=requantize, backend=backend,
        threshold=threshold, c=c, shrink_k=shrink_k,
        max_backtracks=max_backtracks, real_signal=real_signal, nonneg=nonneg,
        with_trace=with_trace, scale_granularity=scale_granularity,
        group_size=group_size, early_exit=early_exit, exit_tol=exit_tol)
    return IHTResult(
        x=res.x[0],
        trace=jax.tree_util.tree_map(lambda t: t[:, 0], res.trace),
        iters_run=res.iters_run[0],
    )


@partial(jax.jit, static_argnames=_STATIC)
def qniht_batch(
    phi: jax.Array,
    Y: jax.Array,
    s: int,
    n_iters: int = 50,
    *,
    bits_phi: Optional[int] = None,
    bits_y: Optional[int] = None,
    key: Optional[jax.Array] = None,
    requantize: str = "pair",
    backend: str = "dense",
    threshold: str = "topk",
    c: float = 0.01,
    shrink_k: float = 2.0,
    max_backtracks: int = 30,
    real_signal: bool = False,
    nonneg: bool = False,
    with_trace: bool = True,
    scale_granularity: str = "per_tensor",
    group_size: Optional[int] = None,
    early_exit: bool = False,
    exit_tol: float = 0.0,
) -> IHTResult:
    """Recover B observation vectors of the same Φ at once (heavy-traffic mode).

    ``Y`` is (B, M); returns x of shape (B, N) and trace arrays (n_iters, B).
    ``phi`` may be a dense (M, N) array or a matrix-free operator, exactly as
    in :func:`qniht` (operator ``mv``/``rmv`` batch over the leading axis).
    One quantized/packed Φ̂ serves the whole batch: each iteration's matvecs are
    single (B, ·) matmuls / qmm kernel calls, so the Φ̂ bytes stream ONCE per
    application for all B problems — with ``backend="packed"`` the amortized
    traffic per problem is ``size(Φ̂_packed)/B``. Per-problem step sizes,
    acceptance tests, and backtracking are vmapped row logic. Row ``b`` matches
    ``qniht(phi, Y[b], ..., key=key)`` up to f32 accumulation order (defaults
    included: both sides default to ``requantize="pair"``; the packed backend
    requires ``requantize="fixed"`` explicitly, same as ``qniht``).

    ``early_exit=True`` skips remaining iteration bodies once EVERY row has
    reached a bitwise fixed point — bit-identical output, cheaper tail
    (stationary operators only; see :func:`_segment_core`). Most valuable
    through :func:`qniht_batch_sharded`, where the all-rows condition is per
    shard rather than per batch.
    """
    if Y.ndim != 2:
        raise ValueError("qniht_batch expects Y of shape (B, M); use qniht for one y")
    return _qniht_core(
        phi, Y, key, n_iters=n_iters, s=s, bits_phi=bits_phi,
        bits_y=bits_y, requantize=requantize, backend=backend,
        threshold=threshold, c=c, shrink_k=shrink_k,
        max_backtracks=max_backtracks, real_signal=real_signal, nonneg=nonneg,
        with_trace=with_trace, scale_granularity=scale_granularity,
        group_size=group_size, early_exit=early_exit, exit_tol=exit_tol)


def qniht_batch_sharded(
    phi,
    Y: jax.Array,
    s: int,
    n_iters: int = 50,
    *,
    mesh=None,
    n_devices: Optional[int] = None,
    bits_phi: Optional[int] = None,
    bits_y: Optional[int] = None,
    key: Optional[jax.Array] = None,
    requantize: str = "pair",
    backend: str = "dense",
    threshold: str = "topk",
    c: float = 0.01,
    shrink_k: float = 2.0,
    max_backtracks: int = 30,
    real_signal: bool = False,
    nonneg: bool = False,
    with_trace: bool = True,
    scale_granularity: str = "per_tensor",
    group_size: Optional[int] = None,
    early_exit: bool = True,
    exit_tol: float = 0.0,
) -> IHTResult:
    """:func:`qniht_batch` with the B axis split over a 1-D ``batch`` device
    mesh — the multi-device serving mode.

    ``mesh`` is a 1-D :class:`jax.sharding.Mesh` whose sole axis is named
    ``"batch"`` (default: all local devices via
    :func:`repro.parallel.batch.make_batch_mesh`; ``n_devices`` limits the
    count). ``Y`` is sharded by rows, Φ̂'s codes/scales (or the matrix-free
    operator's parameters) are replicated, and every piece of per-item solver
    state — ``x``, support, step size µ, backtrack counters, convergence
    flags — lives with its rows. B need not divide the mesh: rows are
    padded to the next multiple with zero rows born converged (so padding
    never delays a shard) and the padding is stripped from the result.

    Contract: item ``b`` computes exactly what ``qniht_batch(phi, Y, ...)``
    computes on one device — same quantization draws (the key is replicated
    and every row folds it exactly as the single-device path does), same
    per-item iterates, up to f32 batching accumulation (the hedge the
    ``qniht_batch`` ↔ ``qniht`` row contract has always carried: results are
    bitwise identical whenever XLA's batched ops are batching-invariant at
    the problem shape, which the test suite pins on an 8-device mesh, and
    differ by ULPs otherwise). Sharding changes only WHERE rows are
    computed, plus the ``early_exit`` default (True here: per-shard
    convergence is the point — a shard of converged rows stops iterating
    instead of riding along with the slowest item in the global batch; see
    :func:`_segment_core`).

    All other arguments exactly as :func:`qniht_batch`, and every backend
    works sharded: dense, fake-quant, packed (all scale granularities), and
    matrix-free operators (Fourier, composed wavelet) — dispatch goes through
    :func:`repro.core.operators.make_iteration_operators` inside each shard.
    """
    if Y.ndim != 2:
        raise ValueError("qniht_batch_sharded expects Y of shape (B, M)")
    from repro.parallel.batch import sharded_qniht_run

    return sharded_qniht_run(
        phi, Y, key, mesh=mesh, n_devices=n_devices, s=s, n_iters=n_iters,
        bits_phi=bits_phi, bits_y=bits_y, requantize=requantize, backend=backend,
        threshold=threshold, c=c, shrink_k=shrink_k, max_backtracks=max_backtracks,
        real_signal=real_signal, nonneg=nonneg, with_trace=with_trace,
        scale_granularity=scale_granularity, group_size=group_size,
        early_exit=early_exit, exit_tol=exit_tol,
    )


def niht(phi, y, s, n_iters=50, **kw) -> IHTResult:
    """Full-precision NIHT (the paper's baseline, Theorem 2 algorithm)."""
    return qniht(phi, y, s, n_iters, bits_phi=None, bits_y=None, **kw)


def stopping_iterations(xs_norm: float, eps_s: float) -> int:
    """Paper's natural stopping criterion n* = ceil(log2(||x^s|| / eps_s))."""
    import math

    if eps_s <= 0 or xs_norm <= 0:
        return 1
    return max(1, math.ceil(math.log2(xs_norm / eps_s)))
