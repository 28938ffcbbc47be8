"""Preemption-safe recovery tests (fast tier, in-process).

The contract under test: the solver loop segmented at ANY iteration boundary —
including through a disk checkpoint and a simulated preemption — produces the
bit-identical result of the one-shot run. The subprocess kill/restart matrix
(real SIGTERM, multi-device meshes) lives in ``tests/test_fault_injection.py``
(slow tier).
"""
import json
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    qniht_batch,
    solver_init,
    solver_result,
    solver_segment,
)
from repro.launch.resilience import Preempted, recover_resilient
from repro.parallel import ChunkJournal, sharded_segment_run
from repro.parallel.batch import BatchServer, pad_state, strip_state
from repro.sensing import make_gaussian_problem
from repro.train.fault import PreemptionGuard, run_with_restarts


def _problem(B=6, m=48, n=96, s=5, key=None):
    key = key if key is not None else jax.random.PRNGKey(3)
    base = make_gaussian_problem(m, n, s, 20.0, key)
    Y = jnp.stack([
        make_gaussian_problem(m, n, s, 20.0, jax.random.fold_in(key, b + 1),
                              phi=base.phi).y for b in range(B)
    ])
    return base.phi, Y, key


def _run_segments(phi, Y, s, n_iters, seg, kw):
    init_kw = {k: v for k, v in kw.items()}
    state = solver_init(phi, Y, s, n_iters, **init_kw)
    seg_kw = {k: v for k, v in kw.items() if k != "key"}
    while int(state.k) < n_iters:
        state = solver_segment(phi, state, seg, s=s, **seg_kw)
    return state


CONFIGS = {
    "fp": dict(),
    "pair": dict(bits_phi=4, bits_y=8, requantize="pair"),
    "packed": dict(bits_phi=4, bits_y=8, requantize="fixed", backend="packed"),
    "freeze": dict(early_exit=True, exit_tol=1e-5),
    "lossless": dict(early_exit=True),
    # what every benchmark cell serves: packed 8-bit codes, lossless early
    # exit, real non-negative signal, no residual trace
    "served": dict(bits_phi=8, bits_y=8, requantize="fixed", backend="packed",
                   early_exit=True, real_signal=True, nonneg=True,
                   with_trace=False),
}


class TestSegmentedSolver:
    @pytest.mark.parametrize("name", sorted(CONFIGS))
    @pytest.mark.parametrize("seg", [1, 7, 30])
    def test_segmented_equals_one_shot(self, name, seg):
        """Any segmentation of [0, n_iters) reproduces qniht_batch bit-for-bit
        — x AND the full per-iteration trace."""
        phi, Y, key = _problem()
        kw = dict(CONFIGS[name])
        if kw.get("bits_phi") or kw.get("bits_y"):
            kw["key"] = key
        ref = qniht_batch(phi, Y, 5, 30, **kw)
        state = _run_segments(phi, Y, 5, 30, seg, kw)
        got = solver_result(state)
        assert bool(jnp.all(ref.x == got.x))
        for a, b in zip(ref.trace, got.trace):
            np.testing.assert_array_equal(np.nan_to_num(np.asarray(a)),
                                          np.nan_to_num(np.asarray(b)))

    def test_sharded_segment_single_device_mesh(self):
        """The shard_map segment engine (width-1 mesh) matches the
        single-process segment path, padding in play (B=5)."""
        phi, Y, key = _problem(B=5)
        kw = dict(bits_y=8, key=key)
        ref = qniht_batch(phi, Y, 5, 20, **kw)
        state = solver_init(phi, Y, 5, 20, **kw)
        while int(state.k) < 20:
            state = sharded_segment_run(phi, state, 7, n_devices=1, s=5, bits_y=8)
        got = solver_result(state)
        assert got.x.shape == ref.x.shape
        assert bool(jnp.all(ref.x == got.x))
        assert bool(jnp.all(ref.trace.mu == got.trace.mu))

    def test_pad_strip_roundtrip(self):
        phi, Y, key = _problem(B=5)
        state = solver_init(phi, Y, 5, 10, key=key)
        padded, b = pad_state(state, 4)
        assert b == 5 and padded.Y.shape[0] == 8
        assert bool(jnp.all(padded.done[5:]))  # pad rows born converged
        back = strip_state(padded, b)
        for leaf_a, leaf_b in zip(jax.tree_util.tree_leaves(state),
                                  jax.tree_util.tree_leaves(back)):
            np.testing.assert_array_equal(np.asarray(leaf_a), np.asarray(leaf_b))

    def test_validation(self):
        phi, Y, key = _problem(B=2)
        state = solver_init(phi, Y, 5, 10)
        with pytest.raises(ValueError, match="n_steps"):
            solver_segment(phi, state, 0, s=5)
        with pytest.raises(ValueError, match="B, M"):
            solver_init(phi, Y[0], 5, 10)


class TestRecoverResilient:
    def test_parity_and_resume(self, tmp_path):
        """Uninterrupted segmented run, then a preempted + resumed run — both
        bit-identical to qniht_batch."""
        phi, Y, key = _problem()
        kw = dict(bits_phi=4, bits_y=8, requantize="pair", key=key)
        ref = qniht_batch(phi, Y, 5, 30, **kw)
        got = recover_resilient(phi, Y, 5, 30, checkpoint_dir=str(tmp_path / "a"),
                                ckpt_every=7, **kw)
        assert bool(jnp.all(ref.x == got.x))
        assert bool(jnp.all(ref.trace.resid_q == got.trace.resid_q))

        class FakeGuard:
            def __init__(self):
                self.polls = 0

            @property
            def requested(self):
                self.polls += 1
                return self.polls >= 2

        d = str(tmp_path / "b")
        with pytest.raises(Preempted) as exc:
            recover_resilient(phi, Y, 5, 30, checkpoint_dir=d, ckpt_every=7,
                              guard=FakeGuard(), **kw)
        assert exc.value.k == 14
        got2 = recover_resilient(phi, Y, 5, 30, checkpoint_dir=d, ckpt_every=7,
                                 resume=True, **kw)
        assert bool(jnp.all(ref.x == got2.x))
        assert bool(jnp.all(ref.trace.mu == got2.trace.mu))

    def test_resume_empty_dir_is_fresh_start(self, tmp_path):
        phi, Y, key = _problem(B=3)
        ref = qniht_batch(phi, Y, 5, 12)
        got = recover_resilient(phi, Y, 5, 12, checkpoint_dir=str(tmp_path),
                                ckpt_every=5, resume=True)
        assert bool(jnp.all(ref.x == got.x))

    def test_resume_falls_back_past_torn_checkpoint(self, tmp_path):
        """Corrupting the newest checkpoint (truncated leaf, then bad manifest
        status) must fall back to the previous one and still finish bitwise."""
        phi, Y, key = _problem(B=3)
        d = str(tmp_path)
        ref = qniht_batch(phi, Y, 5, 20)
        with pytest.raises(Preempted):
            recover_resilient(phi, Y, 5, 20, checkpoint_dir=d, ckpt_every=5,
                              keep=10, guard=type("G", (), {"requested": True})())
        # newest = step_00000005; tear it two ways
        top = os.path.join(d, "step_00000005")
        leaf = os.path.join(top, "leaf_00001.npy")
        with open(leaf, "r+b") as f:
            f.truncate(8)
        got = recover_resilient(phi, Y, 5, 20, checkpoint_dir=d, ckpt_every=5,
                                resume=True)
        assert bool(jnp.all(ref.x == got.x))

    def test_torn_manifest_status(self, tmp_path):
        phi, Y, key = _problem(B=3)
        d = str(tmp_path)
        with pytest.raises(Preempted):
            recover_resilient(phi, Y, 5, 20, checkpoint_dir=d, ckpt_every=5,
                              keep=10, guard=type("G", (), {"requested": True})())
        man = os.path.join(d, "step_00000005", "manifest.json")
        with open(man) as f:
            m = json.load(f)
        m["status"] = "writing"
        with open(man, "w") as f:
            json.dump(m, f)
        # the torn newest checkpoint is invisible; resume restarts from scratch
        # (no earlier step exists) and still matches
        ref = qniht_batch(phi, Y, 5, 20)
        got = recover_resilient(phi, Y, 5, 20, checkpoint_dir=d, ckpt_every=5,
                                resume=True)
        assert bool(jnp.all(ref.x == got.x))

    def test_rejects_unknown_kwargs(self, tmp_path):
        phi, Y, key = _problem(B=2)
        with pytest.raises(TypeError, match="unroll"):
            recover_resilient(phi, Y, 5, 10, checkpoint_dir=str(tmp_path),
                              unroll=4)
        with pytest.raises(ValueError, match="ckpt_every"):
            recover_resilient(phi, Y, 5, 10, checkpoint_dir=str(tmp_path),
                              ckpt_every=0)


class TestChunkJournal:
    def test_drain_and_replay(self, tmp_path):
        phi, Y, key = _problem(B=4)
        d = str(tmp_path)
        keys = [jax.random.fold_in(key, 1000 + ci) for ci in range(3)]
        chunks = [Y, Y * 0.5, Y * 2.0]
        srv = BatchServer(phi, 5, 20, key=key, journal_dir=d)
        ref = [np.asarray(srv.submit(c, k).x) for c, k in zip(chunks, keys)]

        # full drain: nothing re-solved
        srv2 = BatchServer(phi, 5, 20, key=key, journal_dir=d, resume=True)
        got = [np.asarray(srv2.submit(c, k).x) for c, k in zip(chunks, keys)]
        assert srv2.n_drained == 3
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)

        # drop chunk 1's done marker -> demoted to in-flight, replayed to the
        # same bytes
        os.remove(os.path.join(d, "chunk_000001.done.json"))
        j = ChunkJournal(d)
        assert j.completed() == [0, 2] and j.pending() == [1]
        srv3 = BatchServer(phi, 5, 20, key=key, journal_dir=d, resume=True)
        got3 = [np.asarray(srv3.submit(c, k).x) for c, k in zip(chunks, keys)]
        assert srv3.n_drained == 2
        for a, b in zip(ref, got3):
            np.testing.assert_array_equal(a, b)

    def test_divergent_stream_rejected(self, tmp_path):
        phi, Y, key = _problem(B=4)
        srv = BatchServer(phi, 5, 10, key=key, journal_dir=str(tmp_path))
        srv.submit(Y, key)
        srv2 = BatchServer(phi, 5, 10, key=key, journal_dir=str(tmp_path),
                           resume=True)
        with pytest.raises(ValueError, match="journal mismatch"):
            srv2.submit(Y + 1.0, key)

    def test_drained_chunk_placeholder_trace(self, tmp_path):
        phi, Y, key = _problem(B=4)
        srv = BatchServer(phi, 5, 10, key=key, journal_dir=str(tmp_path))
        srv.submit(Y, key)
        srv2 = BatchServer(phi, 5, 10, key=key, journal_dir=str(tmp_path),
                           resume=True)
        r = srv2.submit(Y, key)
        assert r.trace.mu.shape == (10, 4)
        assert bool(jnp.all(jnp.isnan(r.trace.mu)))

    def test_resume_requires_journal(self):
        phi, _, _ = _problem(B=2)
        with pytest.raises(ValueError, match="journal_dir"):
            BatchServer(phi, 5, 10, resume=True)


class TestPreemptionGuard:
    @pytest.mark.parametrize("sig", [signal.SIGTERM, signal.SIGINT])
    def test_both_signals_set_requested(self, sig):
        with PreemptionGuard() as g:
            assert not g.requested
            signal.raise_signal(sig)
            assert g.requested

    def test_restores_previous_handlers(self):
        seen = []
        prev_term = signal.signal(signal.SIGTERM, lambda *a: seen.append("term"))
        prev_int = signal.signal(signal.SIGINT, lambda *a: seen.append("int"))
        try:
            with PreemptionGuard():
                assert signal.getsignal(signal.SIGTERM) is not prev_term
            # both handlers back in place after exit
            signal.raise_signal(signal.SIGTERM)
            signal.raise_signal(signal.SIGINT)
            assert seen == ["term", "int"]
        finally:
            signal.signal(signal.SIGTERM, prev_term)
            signal.signal(signal.SIGINT, prev_int)


class TestRunWithRestarts:
    def test_exponential_backoff_schedule(self):
        delays = []
        calls = []

        def body(attempt):
            calls.append(attempt)
            if attempt < 4:
                raise RuntimeError("boom")
            return "ok"

        out = run_with_restarts(body, max_restarts=4, backoff=1.0,
                                backoff_factor=2.0, max_backoff=3.0,
                                sleep=delays.append)
        assert out == "ok"
        assert calls == [0, 1, 2, 3, 4]
        assert delays == [1.0, 2.0, 3.0, 3.0]  # doubled, then capped

    def test_no_backoff_by_default(self):
        delays = []

        def body(attempt):
            if attempt == 0:
                raise RuntimeError
            return attempt

        assert run_with_restarts(body, sleep=delays.append) == 1
        assert delays == []

    def test_exhausted_restarts_reraise(self):
        with pytest.raises(RuntimeError):
            run_with_restarts(lambda a: (_ for _ in ()).throw(RuntimeError()),
                              max_restarts=2, sleep=lambda _: None)

    def test_preempted_is_retryable(self, tmp_path):
        """Preempted subclasses RuntimeError: a supervised solve that gets
        preempted re-enters with resume and finishes."""
        phi, Y, key = _problem(B=3)
        d = str(tmp_path)
        ref = qniht_batch(phi, Y, 5, 20)

        class OnceGuard:
            def __init__(self):
                self.polls = 0

            @property
            def requested(self):
                self.polls += 1
                return self.polls == 1

        def body(attempt):
            return recover_resilient(
                phi, Y, 5, 20, checkpoint_dir=d, ckpt_every=5,
                resume=attempt > 0, guard=OnceGuard() if attempt == 0 else None)

        got = run_with_restarts(body)
        assert bool(jnp.all(ref.x == got.x))
