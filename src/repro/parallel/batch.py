"""Sharded batch recovery: ``qniht_batch`` split over a 1-D device mesh.

This is the *solver* half of the distribution layer (the model-training half —
parameter sharding rules and compressed gradient collectives — lives in
:mod:`repro.parallel.sharding` and :mod:`repro.parallel.collectives`). Per
Blumensath & Davies' analysis, NIHT iterations for independent observations of
the same Φ̂ never interact: all cross-row structure in ``qniht_batch`` is the
shared operator stream, while step sizes, supports, backtracking, and
convergence are per-row. That makes the B (observations) axis embarrassingly
parallel, and this module maps it onto a mesh:

* **mesh** — 1-D, sole axis named ``"batch"`` (:func:`make_batch_mesh`).
* **sharded** — ``Y`` by rows, and with it every piece of per-item solver
  state inside the loop: ``x``, support masks, µ, backtrack counters, and the
  per-item convergence flags that drive ``early_exit``.
* **replicated** — the operator (dense Φ, packed codes + scales, or a
  matrix-free operator's parameters) and the PRNG key. Each shard re-derives
  exactly the quantization draws the single-device path uses, so per item the
  result is the same computation rather than merely statistically
  equivalent. It is bit-identical only where the compiler sums each row's
  products in the same order at both batch shapes: the tests' sizes on CPU
  are; the LOFAR CS302 int2 batch (16 rows on one TPU v5e chip vs 4 per chip
  on four) differs by up to 1.2e-7 per item.

Implementation: :func:`jax.shard_map` around the shared batched core
``repro.core.niht._qniht_core`` (``check_vma=False`` — the program contains
no collectives, shards are fully independent, so there is nothing for the
varying-manual-axes check to verify). B is
zero-padded up to a multiple of the mesh size; an all-zero row is accepted at
iteration 0 and immediately flagged converged, so padding never slows a shard
down. ``jax.jit`` over static solver config gives the compile cache the
serving loop relies on: a stream of equally-shaped chunks compiles once.

:class:`BatchServer` is the multi-chunk driver: fixed chunk shape, operator
packed ONCE at construction (the packed backend's quantize+pack leaves the
per-chunk path entirely), per-chunk observation keys. This is the layer the
heavy-traffic scenarios (MRI fleets, telescope streams) sit on.

User-facing entry points: :func:`repro.core.niht.qniht_batch_sharded`,
``repro.launch.recover --batch B --devices N``, and
``python -m repro.launch.serve``. See ``docs/architecture.md`` for where this
sits in the layer map.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.niht import (
    _SEG_DEFAULTS,
    _SEG_STATIC,
    _STATIC,
    IHTResult,
    IHTTrace,
    SolverState,
    _qniht_core,
    _segment_core,
    _validate,
)
from repro.core.operators import PackedStreamingOperator
from repro.parallel.journal import ChunkJournal
from repro.quant.formats import as_granularity

BATCH_AXIS = "batch"


def force_host_devices(n: int, env=None) -> None:
    """Append ``--xla_force_host_platform_device_count=n`` to XLA_FLAGS in
    ``env`` (default ``os.environ``). The CPU platform reads the flag ONCE,
    at backend initialization, so this must run before the first jax call of
    the target process; it is harmless on non-CPU platforms and merely
    appends for an already-initialized backend. The single owner of this
    contract — the CLIs and the scaling benchmark all call it.
    """
    import os

    target = os.environ if env is None else env
    target["XLA_FLAGS"] = (target.get("XLA_FLAGS", "")
                           + f" --xla_force_host_platform_device_count={int(n)}")

# the solver's own static-argname list — shared, not copied, so a kwarg added
# to the single-device jit can never silently become a traced argument here
_CORE_STATICS = _STATIC

# x is (B_local, N) → rows sharded; trace arrays are (n_iters, B_local) → the
# batch axis is second; iters_run is (B_local,), each shard's own trip count
# repeated on its rows. The operator/key inputs are replicated (P() prefix).
_OUT_SPECS = IHTResult(
    x=P(BATCH_AXIS),
    trace=IHTTrace(*([P(None, BATCH_AXIS)] * 5)),
    iters_run=P(BATCH_AXIS),
)


def make_batch_mesh(n_devices: Optional[int] = None, devices=None) -> Mesh:
    """1-D serving mesh over the local devices, axis name ``"batch"``.

    ``n_devices`` takes the first N local devices (all of them by default).
    On CPU, force a multi-device view with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` **before** the
    first jax call — see ``docs/benchmarks.md``.
    """
    devs = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        if n_devices < 1 or n_devices > len(devs):
            raise ValueError(
                f"n_devices={n_devices} but {len(devs)} device(s) visible; on CPU "
                "set XLA_FLAGS=--xla_force_host_platform_device_count before jax "
                "initializes")
        devs = devs[:n_devices]
    return Mesh(np.array(devs).reshape(len(devs)), (BATCH_AXIS,))


def pad_batch(Y: jax.Array, n_shards: int) -> tuple[jax.Array, int]:
    """Zero-pad rows of (B, M) ``Y`` up to a multiple of ``n_shards``.

    Returns ``(Y_padded, B)``. Zero rows are free riders: NIHT accepts x = 0
    for y = 0 at the first iteration, so the convergence flag of a padding row
    is set immediately and ``early_exit`` shards never wait on it.
    """
    b = Y.shape[0]
    b_pad = -(-b // n_shards) * n_shards
    if b_pad == b:
        return Y, b
    pad = jnp.zeros((b_pad - b, Y.shape[1]), Y.dtype)
    return jnp.concatenate([Y, pad], axis=0), b


@partial(jax.jit, static_argnames=("mesh",) + _CORE_STATICS)
def _sharded_call(phi, Y, key, *, mesh, **statics):
    def local(phi_, Y_, key_):
        return _qniht_core(
            phi_, Y_, statics["s"], statics["n_iters"], statics["bits_phi"],
            statics["bits_y"], key_, statics["requantize"], statics["backend"],
            statics["threshold"], statics["c"], statics["shrink_k"],
            statics["max_backtracks"], statics["real_signal"], statics["nonneg"],
            statics["with_trace"], statics["scale_granularity"],
            statics["group_size"], statics["early_exit"], statics["exit_tol"],
            statics["unroll"],
        )

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(BATCH_AXIS), P()),
        out_specs=_OUT_SPECS,
        check_vma=False,
    )
    return fn(phi, Y, key)


def sharded_qniht_run(phi, Y, key, *, mesh=None, n_devices=None, **statics) -> IHTResult:
    """Pad → shard_map the batched NIHT core → strip padding.

    The backend of :func:`repro.core.niht.qniht_batch_sharded`; call that
    instead (it validates the solver configuration first).
    """
    mesh = mesh if mesh is not None else make_batch_mesh(n_devices)
    if set(mesh.axis_names) != {BATCH_AXIS}:
        raise ValueError(
            f"qniht_batch_sharded needs a 1-D ('{BATCH_AXIS}',) mesh, got axes "
            f"{mesh.axis_names}; build one with repro.parallel.batch.make_batch_mesh")
    Y_pad, b = pad_batch(Y, mesh.devices.size)
    res = _sharded_call(phi, Y_pad, key, mesh=mesh, **statics)
    if Y_pad.shape[0] == b:
        return res
    return IHTResult(
        x=res.x[:b],
        trace=jax.tree_util.tree_map(lambda t: t[:, :b], res.trace),
        iters_run=res.iters_run[:b],
    )


# SolverState sharding: every per-row leaf splits by rows (trace second axis),
# the iteration index and PRNG key are replicated — _segment_core guarantees k
# lands on min(k + n_steps, n_iters) on every shard (early-exited shards FILL
# their remaining trace rows), so the replicated out-spec is genuine.
_SEG_SPECS = SolverState(
    k=P(), X=P(BATCH_AXIS), done=P(BATCH_AXIS), streak=P(BATCH_AXIS),
    last=IHTTrace(*([P(BATCH_AXIS)] * 5)),
    trace=IHTTrace(*([P(None, BATCH_AXIS)] * 5)),
    Y=P(BATCH_AXIS), key=P(),
)


def pad_state(state: SolverState, n_shards: int) -> tuple[SolverState, int]:
    """Zero-pad a :class:`SolverState`'s rows up to a multiple of ``n_shards``.

    Returns ``(state_padded, B)``. Pad rows are ``Y = 0, X = 0, done = True``:
    x = 0 is a bitwise fixed point of the iteration map for y = 0, so a pad
    row never changes, never delays a shard under ``early_exit``, and — the
    elastic-resume property — padding a state to ANY width and stripping it
    back is the identity on the real rows. A checkpoint is always saved
    stripped (:func:`strip_state`), so it restores onto any mesh.
    """
    b = state.Y.shape[0]
    b_pad = -(-b // n_shards) * n_shards
    if b_pad == b:
        return state, b
    p = b_pad - b

    def rows(a, fill=0):
        pad = jnp.full((p,) + a.shape[1:], fill, a.dtype)
        return jnp.concatenate([a, pad], axis=0)

    return SolverState(
        k=state.k,
        X=rows(state.X),
        done=rows(state.done, True),
        streak=rows(state.streak),
        last=jax.tree_util.tree_map(rows, state.last),
        trace=jax.tree_util.tree_map(
            lambda t: jnp.concatenate(
                [t, jnp.zeros(t.shape[:1] + (p,) + t.shape[2:], t.dtype)], axis=1),
            state.trace),
        Y=rows(state.Y),
        key=state.key,
    ), b


def strip_state(state: SolverState, b: int) -> SolverState:
    """Drop pad rows again (inverse of :func:`pad_state` on the real rows)."""
    if state.Y.shape[0] == b:
        return state
    return SolverState(
        k=state.k, X=state.X[:b], done=state.done[:b], streak=state.streak[:b],
        last=jax.tree_util.tree_map(lambda t: t[:b], state.last),
        trace=jax.tree_util.tree_map(lambda t: t[:, :b], state.trace),
        Y=state.Y[:b], key=state.key,
    )


def refill_rows(state: SolverState, rows, Y_rows, live) -> SolverState:
    """Splice fresh observations into ``rows`` of a :class:`SolverState`,
    leaving every other row **bitwise untouched** — the continuous-batching
    refill primitive (:mod:`repro.parallel.scheduler`).

    ``rows`` is a sequence of distinct row indices, ``Y_rows`` the matching
    ``(len(rows), M)`` observation block, and ``live`` a boolean per row:
    ``True`` re-initializes the row for a new request (``X = 0``, ``done =
    False``, fresh streak/trace), ``False`` turns it into a *pad* row
    (``Y`` must be zero; ``done = True`` makes it a bitwise fixed point the
    segment loop never waits on — same free-rider argument as
    :func:`pad_state`).

    A spliced row matches :func:`repro.core.niht.solver_init`'s row-0 state
    except ``last``'s residual markers, which are zeroed rather than NaN so a
    spliced state stays NaN-free under ``repro.analysis.sanitize`` (the NaN
    marker is a cosmetic "not recorded yet" value; it never feeds ``X``).

    Purity contract: the functional ``.at[rows]`` scatters rewrite ONLY the
    targeted rows; every other row of every leaf — ``X``, ``done``,
    ``streak``, ``last``, the trace *columns*, ``Y`` — keeps its exact bits
    (pinned by tests/test_scheduler.py::TestSplicePurity).
    """
    rows = [int(r) for r in rows]
    if len(set(rows)) != len(rows):
        raise ValueError(f"refill_rows needs distinct rows, got {rows}")
    b = state.Y.shape[0]
    if any(r < 0 or r >= b for r in rows):
        raise ValueError(f"rows {rows} out of range for B={b}")
    Y_rows = jnp.asarray(Y_rows, state.Y.dtype)
    if Y_rows.shape != (len(rows), state.Y.shape[1]):
        raise ValueError(
            f"Y_rows shape {Y_rows.shape} != {(len(rows), state.Y.shape[1])}")
    live = tuple(bool(v) for v in live)
    if len(live) != len(rows):
        raise ValueError(f"live must be one flag per row, got {len(live)}")
    return _splice_rows(state, Y_rows, rows=tuple(rows), live=live)


@partial(jax.jit, static_argnames=("rows", "live"))
def _splice_rows(state: SolverState, Y_rows, *, rows, live) -> SolverState:
    # rows/live are static: the refill loop revisits a small set of splice
    # patterns (deterministic trace ⇒ deterministic patterns), and a fused
    # scatter program per pattern keeps the per-tick cost off the eager
    # dispatch path
    idx = np.asarray(rows, np.int32)
    live_v = jnp.asarray(np.asarray(live, bool))

    def zero_rows(a):
        return a.at[idx].set(jnp.zeros((len(rows),) + a.shape[1:], a.dtype))

    return SolverState(
        k=state.k,
        X=zero_rows(state.X),
        done=state.done.at[idx].set(~live_v),
        streak=zero_rows(state.streak),
        last=jax.tree_util.tree_map(zero_rows, state.last),
        trace=jax.tree_util.tree_map(
            lambda t: t.at[:, idx].set(
                jnp.zeros(t.shape[:1] + (len(rows),) + t.shape[2:], t.dtype)),
            state.trace),
        Y=state.Y.at[idx].set(Y_rows),
        key=state.key,
    )


def state_shardings(mesh: Mesh) -> SolverState:
    """NamedSharding tree placing a (padded) :class:`SolverState` on ``mesh``
    per ``_SEG_SPECS`` — the elastic re-placement step: a state computed on
    (or restored from a checkpoint written under) one mesh is explicitly
    re-sharded for the target mesh before the next segment."""
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), _SEG_SPECS,
        is_leaf=lambda x: isinstance(x, P))


@partial(jax.jit, static_argnames=("mesh",) + _SEG_STATIC)
def _sharded_segment_call(phi, state, *, mesh, n_steps, **statics):
    fn = jax.shard_map(
        lambda phi_, st: _segment_core(phi_, st, n_steps=n_steps, **statics),
        mesh=mesh,
        in_specs=(P(), _SEG_SPECS),
        out_specs=_SEG_SPECS,
        check_vma=False,
    )
    return fn(phi, state)


def sharded_segment_run(phi, state: SolverState, n_steps: int, *, mesh=None,
                        n_devices: Optional[int] = None, **statics) -> SolverState:
    """:func:`repro.core.niht.solver_segment` with the state's rows split over
    a ``("batch",)`` mesh — the segment engine of the preemption-safe driver
    (:mod:`repro.launch.resilience`).

    Pads the state to the mesh width, advances ``n_steps`` iterations under
    ``shard_map``, and strips the padding again, so the returned (and
    checkpointed) state never records the mesh it ran on: save at ``--devices
    4``, resume at ``--devices 2`` — elastic by construction. Per-item
    bit-identity vs the single-process :func:`solver_segment` carries the same
    batching-invariance hedge as :func:`qniht_batch_sharded`, pinned bitwise
    in the fault-injection tests.
    """
    if n_steps < 1:
        raise ValueError(f"n_steps must be >= 1, got {n_steps}")
    mesh = mesh if mesh is not None else make_batch_mesh(n_devices)
    if set(mesh.axis_names) != {BATCH_AXIS}:
        raise ValueError(
            f"sharded_segment_run needs a 1-D ('{BATCH_AXIS}',) mesh, got axes "
            f"{mesh.axis_names}; build one with repro.parallel.batch.make_batch_mesh")
    statics = {**_SEG_DEFAULTS, **statics}
    _validate(phi, statics["bits_phi"], statics["bits_y"], state.key,
              statics["requantize"], statics["backend"], statics["threshold"],
              statics["real_signal"], statics["scale_granularity"],
              statics["group_size"], statics["early_exit"], statics["exit_tol"])
    state_p, b = pad_state(state, mesh.devices.size)
    # elastic: the incoming state may be committed to a different mesh width
    # (a previous segment's placement, or a checkpoint restored as host
    # arrays) — re-place it for THIS mesh before the sharded call
    state_p = jax.device_put(state_p, state_shardings(mesh))
    out = _sharded_segment_call(phi, state_p, mesh=mesh, n_steps=n_steps, **statics)
    return strip_state(out, b)


class BatchServer:
    """Multi-chunk sharded recovery service: the serving loop's driver.

    Holds one measurement operator and one solver configuration, and solves a
    stream of equally-shaped ``(B, M)`` observation chunks over a fixed
    ``batch`` mesh. Amortization contract:

    * **pack once** — with ``backend="packed"``, Φ̂ is quantized and packed at
      construction (keyed exactly as the solver would: ``fold_in(kφ, 0)`` of
      the construction key's second split half), and every chunk streams the
      same codes. ``submit`` then runs the matrix-free operator path, so the
      per-chunk program contains no quantize/pack at all.
    * **compile once** — the sharded call jits on (chunk shape, static solver
      config, mesh); a stream of same-shaped chunks reuses one executable.
      ``compile_cache_keys`` exposes the distinct shapes seen so far.
    * **per-chunk keys** — ``submit(Y, key=k)`` draws the chunk's observation
      quantization from ``k`` (default: the construction key), replicated so
      each row folds it the same way the single-device path would.

    Bit-identity: with construction key K and ``submit(Y, key=K)``, row ``b``
    equals ``qniht_batch(phi, Y, ..., key=K)`` of the corresponding
    single-device backend configuration bit-for-bit (the parity test in
    ``tests/test_sharded_batch.py`` pins this).

    Restartability: with ``journal_dir`` set, every chunk is write-ahead
    journaled (:class:`repro.parallel.journal.ChunkJournal`) — inputs before
    the solve, result after. A restarted server constructed with the same
    ``journal_dir`` and ``resume=True``, fed the same deterministic stream,
    **drains** already-completed chunks from disk (their solve is skipped;
    ``n_drained`` counts them) and **replays** in-flight ones; the resulting
    ``x`` stream is bit-identical to the uninterrupted run. Drained chunks
    carry a NaN/zero placeholder trace and ``iters_run`` of zeros (the journal
    persists ``x``, the serving product — traces are diagnostics; re-run
    without a kill if you need them).
    """

    def __init__(self, phi, s: int, n_iters: int = 50, *, mesh=None,
                 n_devices: Optional[int] = None,
                 bits_phi: Optional[int] = None, bits_y: Optional[int] = None,
                 key: Optional[jax.Array] = None, requantize: str = "fixed",
                 backend: str = "dense", threshold: str = "topk",
                 c: float = 0.01, shrink_k: float = 2.0, max_backtracks: int = 30,
                 real_signal: bool = False, nonneg: bool = False,
                 with_trace: bool = False,
                 scale_granularity: str = "per_tensor",
                 group_size: Optional[int] = None, early_exit: bool = True,
                 exit_tol: float = 0.0, unroll: int = 1,
                 journal_dir: Optional[str] = None, resume: bool = False):
        _validate(phi, bits_phi, bits_y, key, requantize, backend, threshold,
                  real_signal, scale_granularity, group_size, early_exit,
                  exit_tol, unroll)
        if resume and journal_dir is None:
            raise ValueError("resume=True needs a journal_dir to resume from")
        self.mesh = mesh if mesh is not None else make_batch_mesh(n_devices)
        self.key = key if key is not None else jax.random.PRNGKey(0)
        self.phi = phi
        self.journal = ChunkJournal(journal_dir) if journal_dir is not None else None
        self._resume = bool(resume)
        self.n_chunks = 0
        self.n_items = 0
        self.n_drained = 0
        self._shapes: set = set()
        statics = dict(
            s=s, n_iters=n_iters, bits_phi=bits_phi, bits_y=bits_y,
            requantize=requantize, backend=backend, threshold=threshold, c=c,
            shrink_k=shrink_k, max_backtracks=max_backtracks,
            real_signal=real_signal, nonneg=nonneg, with_trace=with_trace,
            scale_granularity=scale_granularity, group_size=group_size,
            early_exit=early_exit, exit_tol=exit_tol, unroll=unroll,
        )
        if backend == "packed":
            # Pack once with the exact key the in-loop pack would fold, then
            # serve through the operator path: per-chunk programs stream the
            # codes but never re-quantize (see repro.core.operators).
            _, kphi = jax.random.split(self.key)
            self.phi = PackedStreamingOperator.pack(
                phi, bits_phi, jax.random.fold_in(kphi, 0),
                granularity=as_granularity(scale_granularity, group_size))
            statics.update(bits_phi=None, backend="dense")
        self._statics = statics

    def submit(self, Y: jax.Array, key: Optional[jax.Array] = None,
               row_mask=None) -> IHTResult:
        """Solve one (B, M) chunk; returns the usual :class:`IHTResult`.

        ``row_mask`` (optional (B,) bool) marks which rows are live user
        requests. The historical contract was all-rows-live; callers that pad
        a partial final chunk (or splice harvested rows) pass the mask so
        padded rows are never journaled as user results: masked rows of ``Y``
        are zeroed before the solve (an all-zero row fixes at ``x = 0``), the
        journal stores only the valid rows of ``x``, and a drained chunk
        reconstructs the full shape with zeros at the invalid rows —
        bit-identical to the live solve.

        With a journal: the chunk index is this server's submission count, the
        inputs (mask included) are journaled before the solve and the result
        after. Under ``resume=True`` a chunk whose result is already journaled
        is drained from disk instead of solved (see the class docstring).
        """
        if Y.ndim != 2:
            raise ValueError(f"BatchServer.submit expects (B, M) chunks, got {Y.shape}")
        mask = ChunkJournal._norm_mask(row_mask, Y.shape[0])
        if mask is not None:
            Y = jnp.where(jnp.asarray(mask, bool)[:, None], Y,
                          jnp.zeros_like(Y))
        idx = self.n_chunks
        self.n_chunks += 1
        self.n_items += Y.shape[0] if mask is None else int(mask.sum())
        k = key if key is not None else self.key
        if self.journal is not None:
            if self._resume and self.journal.is_complete(idx):
                self.journal.verify_submit(idx, Y, k, mask)
                self.n_drained += 1
                return IHTResult(x=jnp.asarray(self.journal.load_result_full(idx)),
                                 trace=self._placeholder_trace(Y.shape[0]),
                                 iters_run=jnp.zeros((Y.shape[0],), jnp.int32))
            self.journal.record_submit(idx, Y, k, mask)
        self._shapes.add(Y.shape)
        res = sharded_qniht_run(self.phi, Y, k, mesh=self.mesh, **self._statics)
        if self.journal is not None:
            self.journal.record_result(idx, res.x, mask)
        return res

    def _placeholder_trace(self, b: int) -> IHTTrace:
        """Trace shell for a drained chunk (the journal persists only x)."""
        n_iters = self._statics["n_iters"]
        # np-built: an eager jnp.full(nan) would trip jax_debug_nans
        # under --sanitize even though this NaN means "not recorded"
        nanbuf = jnp.asarray(np.full((n_iters, b), np.nan, np.float32))
        return IHTTrace(resid_q=nanbuf, resid_true=nanbuf, mu=nanbuf,
                        support_changed=jnp.zeros((n_iters, b), bool),
                        backtracks=jnp.zeros((n_iters, b), jnp.int32))

    def serve(self, chunks, keys=None):
        """Drive a stream: yields one :class:`IHTResult` per chunk. ``keys``
        (optional iterable, any kind — generator included) supplies per-chunk
        observation keys; when exhausted or None, chunks fall back to the
        construction key."""
        key_iter = iter(keys) if keys is not None else None
        for Y in chunks:
            k = next(key_iter, None) if key_iter is not None else None
            yield self.submit(Y, k)

    @property
    def n_shards(self) -> int:
        return int(self.mesh.devices.size)

    @property
    def compile_cache_keys(self) -> tuple:
        """Distinct chunk shapes seen (each costs one compile per config)."""
        return tuple(sorted(self._shapes))
