"""Preemption-safe segmented recovery: checkpointed solves that resume bit-identically.

The solver loop (:mod:`repro.core.niht`) is a deterministic iteration map —
every stochastic input (the ŷ draw, the per-iteration Φ̂ pair) is re-derived
from ``(Y, key)`` and the body consumes the global iteration index. That makes
any iteration boundary an exact restart point, and this module turns that into
an operational property:

* :func:`recover_resilient` runs ``qniht_batch`` (or its mesh-sharded twin) in
  segments of ``ckpt_every`` iterations, persisting the full
  :class:`~repro.core.niht.SolverState` through
  :mod:`repro.train.checkpoint`'s atomic tmp→rename + manifest protocol after
  every segment.
* A ``kill -TERM``/``-INT`` mid-run is absorbed by
  :class:`~repro.train.fault.PreemptionGuard`: the in-flight segment finishes,
  one final *synchronous* checkpoint is written, and :class:`Preempted` is
  raised (a ``RuntimeError`` — :func:`~repro.train.fault.run_with_restarts`
  retries it by default).
* Restarting with ``resume=True`` restores the newest complete checkpoint —
  falling back past torn ones — and continues; the finished result is
  **bit-identical** to the uninterrupted run (pinned in
  ``tests/test_fault_injection.py``).
* Checkpoints are **elastic**: the state is saved stripped of mesh padding, so
  a run checkpointed at ``--devices 4`` resumes at ``--devices 2`` (or on a
  single device) with the same bits — see
  :func:`repro.parallel.batch.pad_state`.

CLI: ``python -m repro.launch.recover --checkpoint-dir CKPT --ckpt-every 10
[--resume]``; the serving loop's chunk-level analogue (write-ahead journal) is
``python -m repro.launch.serve --checkpoint-dir`` — see
``docs/fault-tolerance.md``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.niht import (
    _SEG_DEFAULTS,
    IHTResult,
    SolverState,
    solver_init,
    solver_result,
    solver_segment,
)
from repro.core.operators import PackedStreamingOperator
from repro.quant.formats import as_granularity
from repro.train.checkpoint import restore_latest, save
from repro.train.fault import PreemptionGuard

__all__ = ["Preempted", "recover_resilient"]


class Preempted(RuntimeError):
    """A solve was interrupted by SIGTERM/SIGINT after a durable checkpoint.

    The run can be resumed (``resume=True``, same arguments) and will finish
    bit-identically. Subclasses ``RuntimeError`` so the default ``retry_on`` of
    :func:`repro.train.fault.run_with_restarts` re-enters the solve in-process.
    """

    def __init__(self, k: int, checkpoint_dir: str):
        super().__init__(
            f"preempted at iteration {k}; checkpoint written to {checkpoint_dir}")
        self.k = k
        self.checkpoint_dir = checkpoint_dir


def recover_resilient(
    phi, Y: jax.Array, s: int, n_iters: int = 50, *,
    checkpoint_dir: str, ckpt_every: int = 10, resume: bool = False,
    mesh=None, n_devices: Optional[int] = None, keep: int = 3,
    async_save: bool = False, guard: Optional[PreemptionGuard] = None,
    verbose: bool = False, key: Optional[jax.Array] = None,
    **solver_kw,
) -> IHTResult:
    """``qniht_batch(phi, Y, s, n_iters, ...)`` with segment checkpoints.

    Accepts the batched solver's keyword configuration (``bits_phi``,
    ``backend`` ...).
    ``mesh``/``n_devices`` selects the sharded segment engine
    (:func:`repro.parallel.batch.sharded_segment_run`); the checkpoint itself
    is mesh-agnostic either way.

    ``guard``: an *entered* :class:`PreemptionGuard` to poll between segments;
    ``None`` installs one for the duration of this call (SIGTERM/SIGINT →
    final synchronous checkpoint → :class:`Preempted`). ``async_save``
    overlaps checkpoint I/O with the next segment; the final checkpoint (and
    a preemption's last one) is always synchronous, and concurrent writers
    are serialized by the checkpoint layer's per-directory lock.
    """
    if ckpt_every < 1:
        raise ValueError(f"ckpt_every must be >= 1, got {ckpt_every}")
    unknown = set(solver_kw) - set(_SEG_DEFAULTS)
    if unknown:
        raise TypeError(f"recover_resilient got unexpected solver kwargs {sorted(unknown)}")
    statics = {**_SEG_DEFAULTS, **solver_kw}
    key = key if key is not None else jax.random.PRNGKey(0)

    # the restore target carries shapes/dtypes only — no data is touched, and
    # validation runs on the user-facing configuration
    target = jax.eval_shape(
        lambda: solver_init(phi, Y, s, n_iters, key=key, **statics))
    state, step = (restore_latest(checkpoint_dir, target) if resume
                   else (None, None))
    if state is None:
        state = solver_init(phi, Y, s, n_iters, key=key, **statics)
        if verbose:
            print(f"[resilience] fresh start, n_iters={n_iters}", flush=True)
    elif verbose:
        print(f"[resilience] resumed from step {step} (k={int(state.k)})", flush=True)

    # pack once, exactly as BatchServer does: the packed codes are a
    # deterministic function of (phi, key), so a restarted process rebuilds
    # the identical stream — nothing operator-side needs checkpointing
    seg_phi, seg_statics = phi, dict(statics)
    if statics["backend"] == "packed":
        _, kphi = jax.random.split(key)
        seg_phi = PackedStreamingOperator.pack(
            phi, statics["bits_phi"], jax.random.fold_in(kphi, 0),
            granularity=as_granularity(statics["scale_granularity"],
                                       statics["group_size"]))
        seg_statics.update(bits_phi=None, backend="dense")

    def segment(st: SolverState, n: int) -> SolverState:
        if mesh is not None or n_devices:
            from repro.parallel.batch import sharded_segment_run

            return sharded_segment_run(seg_phi, st, n, mesh=mesh,
                                       n_devices=n_devices, s=s, **seg_statics)
        return solver_segment(seg_phi, st, n, s=s, **seg_statics)

    g = guard if guard is not None else PreemptionGuard().__enter__()
    try:
        while int(state.k) < n_iters:
            n = min(ckpt_every, n_iters - int(state.k))
            state = segment(state, n)
            jax.block_until_ready(state.X)
            k = int(state.k)
            final = k >= n_iters
            preempt = g.requested
            # preemption and the horizon both demand a durable (synchronous)
            # write before we let go of the process
            save(checkpoint_dir, k, state, keep=keep,
                 async_=async_save and not final and not preempt)
            if verbose:
                print(f"[resilience] k={k}/{n_iters} checkpointed", flush=True)
            if preempt and not final:
                raise Preempted(k, checkpoint_dir)
    finally:
        if guard is None:
            g.__exit__(None, None, None)
    return solver_result(state)
