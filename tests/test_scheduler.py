"""Continuous-batching scheduler tests (repro.parallel.scheduler).

Three layers, mirroring the module's contracts:

* **Differential parity** (the tentpole contract): for fuzzed arrival
  orders, priorities, slot counts, segment lengths, and per-request
  horizons, every request recovered through the continuous scheduler is
  **bitwise** equal to its standalone solve — ``qniht_batch`` over
  ``[y, 0, ..., 0]`` at the scheduler's slot width with the same key
  (``ContinuousScheduler.reference_solve``). The fuzz is seeded-numpy
  parametrization (13 seeds x 4 solver configs x 5 requests = 260 cases,
  guaranteed to run with or without hypothesis); a hypothesis variant rides
  along through the shim when the package is installed.

  The reference is deliberately the request *at slot width*, not a ``B = 1``
  solve: XLA lowers a one-row batch through a different gemv kernel whose
  accumulation differs in the last ulp, so single-row parity is not a
  property any scheduler could have. Fixed-width row independence is the
  property that holds, and these tests are what pin it.

* **Queue/scheduling invariants**: FIFO within a priority class, bounded
  wait under aging (no starvation), deadline-expired requests shed with a
  reported status rather than solved late, shed-on-overflow with
  urgency-based eviction, and decision-log determinism given (seed, arrival
  trace).

* **State purity**: splicing a row via ``refill_rows`` leaves every other
  row of every ``SolverState`` leaf — ``done``/``streak``/``last``/trace
  *columns* included — bit-identical, both immediately and after the next
  segment (the failure mode lockstep parity tests can't see).

The multi-device case runs in a subprocess with 4 forced host devices (slow
tier, per the dry-run rule). It uses slots=8 so every shard holds >= 2 rows:
at 1 row per shard XLA again picks the gemv path and parity degrades to
ulp-level — the same hedge tests/test_sharded_batch.py carries.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_shim import HAVE_HYPOTHESIS, given, settings, st
from repro.core.niht import solver_init, solver_segment
from repro.parallel import (
    AdmissionQueue,
    BatchServer,
    ChunkJournal,
    ContinuousScheduler,
    Request,
    make_batch_mesh,
    refill_rows,
)

M, N, S, N_ITERS = 16, 32, 3, 12
KEY = jax.random.PRNGKey(7)


def _phi():
    rng = np.random.default_rng(42)
    return jnp.asarray(rng.standard_normal((M, N)) / np.sqrt(M), jnp.float32)


PHI = _phi()


def _mk_y(rng):
    x = np.zeros(N, np.float32)
    x[rng.choice(N, S, replace=False)] = rng.standard_normal(S).astype(np.float32)
    return np.asarray(PHI) @ x


def _fuzz_trace(rng, n_req):
    """Arrivals with fuzzed ticks (bursty: repeated ticks), priorities, and
    per-request horizons."""
    ticks = np.cumsum(rng.integers(0, 3, n_req))
    return [
        (int(ticks[i]),
         Request(rid=i, y=_mk_y(rng), priority=int(rng.integers(0, 3)),
                 n_iters=int(rng.choice([4, 8, 12]))))
        for i in range(n_req)
    ]


# the early_exit-compatible solver configs: full precision (exact bitwise
# fixed-point rule), the freeze rule, fake-quant int8 fixed, packed int4
CONFIGS = [
    dict(),
    dict(exit_tol=1e-4),
    dict(bits_phi=8, bits_y=8, requantize="fixed"),
    dict(bits_phi=4, bits_y=8, requantize="fixed", backend="packed"),
]
CONFIG_IDS = ["f32", "f32-freeze", "fakequant8", "packed4"]


class TestDifferentialParity:
    @pytest.mark.parametrize("ci", range(len(CONFIGS)), ids=CONFIG_IDS)
    @pytest.mark.parametrize("seed", range(13))
    def test_fuzzed_arrivals_bitwise(self, seed, ci):
        """13 seeds x 4 configs x 5 requests = 260 fuzzed cases: whatever the
        arrival order, co-tenants, slot count, segment length, or refill
        timing, each answer is bitwise its standalone solve."""
        rng = np.random.default_rng(1000 * ci + seed)
        slots = int(rng.integers(2, 5))
        seg_len = int(rng.choice([2, 4]))
        arrivals = _fuzz_trace(rng, 5)
        sch = ContinuousScheduler(PHI, S, N_ITERS, slots=slots,
                                  seg_len=seg_len, key=KEY, queue_depth=32,
                                  **CONFIGS[ci])
        reports = sch.run(arrivals)
        for _, req in arrivals:
            rep = reports[req.rid]
            assert rep.status == "done"
            assert rep.iters_used is not None and rep.iters_used <= req.n_iters
            assert rep.latency_s is not None and rep.latency_s >= 0
            np.testing.assert_array_equal(
                np.asarray(rep.x),
                np.asarray(sch.reference_solve(req.y, req.n_iters)))

    def test_lockstep_same_answers(self):
        """Policy changes when rows run, never what they compute: lockstep
        and continuous produce bitwise identical answers per request."""
        rng = np.random.default_rng(5)
        arrivals = _fuzz_trace(rng, 6)
        outs = {}
        for policy in ("continuous", "lockstep"):
            sch = ContinuousScheduler(PHI, S, N_ITERS, slots=3, seg_len=4,
                                      key=KEY, policy=policy)
            outs[policy] = sch.run(arrivals)
        for _, req in arrivals:
            np.testing.assert_array_equal(
                np.asarray(outs["continuous"][req.rid].x),
                np.asarray(outs["lockstep"][req.rid].x))

    if HAVE_HYPOTHESIS:
        @settings(max_examples=25, deadline=None)
        @given(seed=st.integers(0, 2**31 - 1))
        def test_fuzzed_arrivals_bitwise_hypothesis(self, seed):
            """Hypothesis variant of the differential property (extra cases
            when the optional dependency is installed)."""
            rng = np.random.default_rng(seed)
            arrivals = _fuzz_trace(rng, 4)
            sch = ContinuousScheduler(PHI, S, N_ITERS,
                                      slots=int(rng.integers(2, 5)),
                                      seg_len=int(rng.choice([2, 4])),
                                      key=KEY)
            reports = sch.run(arrivals)
            for _, req in arrivals:
                np.testing.assert_array_equal(
                    np.asarray(reports[req.rid].x),
                    np.asarray(sch.reference_solve(req.y, req.n_iters)))


class TestAdmissionQueue:
    def test_fifo_within_class(self):
        q = AdmissionQueue(depth=8)
        for seq in range(4):
            q.offer(Request(rid=seq, y=np.zeros(M)), tick=0, seq=seq)
        assert [q.pop(1).req.rid for _ in range(4)] == [0, 1, 2, 3]

    def test_strict_priority_between_classes(self):
        q = AdmissionQueue(depth=8)
        q.offer(Request(rid=0, y=np.zeros(M), priority=2), tick=0, seq=0)
        q.offer(Request(rid=1, y=np.zeros(M), priority=0), tick=0, seq=1)
        assert q.pop(0).req.rid == 1

    def test_aging_promotes_old_requests(self):
        q = AdmissionQueue(depth=8, age_every=2)
        q.offer(Request(rid=0, y=np.zeros(M), priority=2), tick=0, seq=0)
        q.offer(Request(rid=1, y=np.zeros(M), priority=0), tick=4, seq=1)
        # at tick 6 the old priority-2 entry has aged to effective 2-3=-1
        assert q.pop(6).req.rid == 0

    def test_overflow_sheds_incoming_unless_more_urgent(self):
        q = AdmissionQueue(depth=1)
        q.offer(Request(rid=0, y=np.zeros(M), priority=1), tick=0, seq=0)
        # equal urgency: incumbent keeps its place (FIFO), incoming shed
        admitted, shed = q.offer(Request(rid=1, y=np.zeros(M), priority=1),
                                 tick=0, seq=1)
        assert not admitted and shed.req.rid == 1
        # strictly more urgent: evicts the incumbent
        admitted, shed = q.offer(Request(rid=2, y=np.zeros(M), priority=0),
                                 tick=0, seq=2)
        assert admitted and shed.req.rid == 0
        assert q.pop(0).req.rid == 2

    def test_shed_expired(self):
        q = AdmissionQueue(depth=8)
        q.offer(Request(rid=0, y=np.zeros(M), deadline=3), tick=0, seq=0)
        q.offer(Request(rid=1, y=np.zeros(M)), tick=0, seq=1)
        assert q.shed_expired(3) == []           # deadline tick itself is ok
        assert [e.req.rid for e in q.shed_expired(4)] == [0]
        assert len(q) == 1


class TestSchedulerInvariants:
    def test_fifo_within_class_end_to_end(self):
        """Same-priority requests start in arrival order (slot contention
        forces queueing)."""
        rng = np.random.default_rng(0)
        arrivals = [(0, Request(rid=i, y=_mk_y(rng), n_iters=4))
                    for i in range(6)]
        sch = ContinuousScheduler(PHI, S, N_ITERS, slots=1, seg_len=4, key=KEY)
        sch.run(arrivals)
        starts = [rid for _, ev, rid, _ in sch.log if ev == "start"]
        assert starts == [0, 1, 2, 3, 4, 5]

    def test_no_starvation_under_aging(self):
        """A low-priority request under a sustained high-priority flood
        starts within ~priority*age_every ticks of arriving — and without
        aging the same trace starves it to the very end."""
        rng = np.random.default_rng(1)
        victim = Request(rid=99, y=_mk_y(rng), priority=2, n_iters=4)
        flood = [(t, Request(rid=t, y=_mk_y(rng), priority=0, n_iters=4))
                 for t in range(20)]
        arrivals = sorted([(0, victim)] + flood, key=lambda a: a[0])
        aged = ContinuousScheduler(PHI, S, N_ITERS, slots=1, seg_len=4,
                                   key=KEY, age_every=2, queue_depth=64)
        rep = aged.run(arrivals)[99]
        assert rep.status == "done"
        assert rep.start_tick <= 2 * 2 + 4   # priority*age_every + drain slack
        starved = ContinuousScheduler(PHI, S, N_ITERS, slots=1, seg_len=4,
                                      key=KEY, age_every=0, queue_depth=64)
        rep0 = starved.run(arrivals)[99]
        assert rep0.start_tick > rep.start_tick  # strict priorities starve it

    def test_deadline_expired_is_shed_not_solved_late(self):
        rng = np.random.default_rng(2)
        long_job = Request(rid=0, y=_mk_y(rng), n_iters=12)
        doomed = Request(rid=1, y=_mk_y(rng), deadline=1, n_iters=4)
        sch = ContinuousScheduler(PHI, S, N_ITERS, slots=1, seg_len=4, key=KEY)
        reports = sch.run([(0, long_job), (0, doomed)])
        rep = reports[1]
        assert rep.status == "shed_deadline"
        assert rep.x is None and rep.finish_tick is not None
        assert 1 not in [rid for _, ev, rid, _ in sch.log if ev == "start"]
        assert reports[0].status == "done"
        assert sch.stats()["n_shed_deadline"] == 1

    def test_deadline_met_requests_run(self):
        """A deadline is the last admissible start tick, not a kill switch:
        a request granted a slot in time runs to completion."""
        rng = np.random.default_rng(3)
        sch = ContinuousScheduler(PHI, S, N_ITERS, slots=2, seg_len=4, key=KEY)
        reports = sch.run([(0, Request(rid=0, y=_mk_y(rng), deadline=5,
                                       n_iters=12))])
        assert reports[0].status == "done"

    def test_queue_overflow_shed_reported(self):
        rng = np.random.default_rng(4)
        blocker = Request(rid=0, y=_mk_y(rng), n_iters=12)
        first = Request(rid=1, y=_mk_y(rng), priority=1, n_iters=4)
        urgent = Request(rid=2, y=_mk_y(rng), priority=0, n_iters=4)
        sch = ContinuousScheduler(PHI, S, N_ITERS, slots=1, seg_len=4,
                                  key=KEY, queue_depth=1)
        # blocker is granted the slot at tick 0; the two rivals then contend
        # for the single queue seat at tick 1
        reports = sch.run([(0, blocker), (1, first), (1, urgent)])
        # the urgent late-comer evicts the queued priority-1 entry
        assert reports[1].status == "shed_queue_full"
        assert reports[2].status == "done"
        assert sch.stats()["n_shed_queue_full"] == 1

    def test_decisions_deterministic_given_trace(self):
        """Same (seed, arrival trace) => identical decision log and bitwise
        identical answers — wall-clock observability never feeds back."""
        rng = np.random.default_rng(6)
        arrivals = _fuzz_trace(rng, 6)
        runs = []
        for _ in range(2):
            sch = ContinuousScheduler(PHI, S, N_ITERS, slots=2, seg_len=4,
                                      key=KEY, queue_depth=3, age_every=2)
            reports = sch.run(arrivals)
            runs.append((sch.log, reports))
        assert runs[0][0] == runs[1][0]
        for rid, rep in runs[0][1].items():
            other = runs[1][1][rid]
            assert rep.status == other.status
            if rep.x is not None:
                np.testing.assert_array_equal(np.asarray(rep.x),
                                              np.asarray(other.x))

    def test_stats_fields(self):
        rng = np.random.default_rng(7)
        sch = ContinuousScheduler(PHI, S, N_ITERS, slots=2, seg_len=4, key=KEY)
        sch.run(_fuzz_trace(rng, 4))
        st_ = sch.stats()
        assert 0 < st_["slot_occupancy"] <= 1
        assert st_["segments_run"] >= 1 and st_["n_done"] == 4
        assert sum(st_["segment_lengths"].values()) == st_["segments_run"]

    def test_input_validation(self):
        rng = np.random.default_rng(8)
        with pytest.raises(ValueError, match="policy"):
            ContinuousScheduler(PHI, S, N_ITERS, policy="roundrobin")
        with pytest.raises(ValueError, match="early_exit"):
            # pair requantize redraws operators: not stationary, no refill
            ContinuousScheduler(PHI, S, N_ITERS, bits_phi=8, bits_y=8,
                                key=KEY, requantize="pair")
        sch = ContinuousScheduler(PHI, S, N_ITERS, slots=2, seg_len=4, key=KEY)
        with pytest.raises(ValueError, match="n_iters"):
            sch.run([(0, Request(rid=0, y=_mk_y(rng), n_iters=99))])
        with pytest.raises(ValueError, match="duplicate"):
            sch2 = ContinuousScheduler(PHI, S, N_ITERS, slots=2, key=KEY)
            sch2.run([(0, Request(rid=1, y=_mk_y(rng), n_iters=4)),
                      (0, Request(rid=1, y=_mk_y(rng), n_iters=4))])
        with pytest.raises(ValueError, match="nondecreasing"):
            sch3 = ContinuousScheduler(PHI, S, N_ITERS, slots=2, key=KEY)
            sch3.run([(3, Request(rid=0, y=_mk_y(rng))),
                      (1, Request(rid=1, y=_mk_y(rng)))])


class TestSplicePurity:
    """The regression the ISSUE names: refilling row b must leave every other
    row of SolverState bit-identical — the failure mode lockstep parity
    can't see (it never splices)."""

    def _advanced_state(self):
        rng = np.random.default_rng(9)
        Y = jnp.stack([jnp.asarray(_mk_y(rng)) for _ in range(4)])
        state = solver_init(PHI, Y, S, n_iters=N_ITERS, early_exit=True)
        return solver_segment(PHI, state, 4, s=S, early_exit=True)

    @staticmethod
    def _rows_equal(a, b, rows, axis=0):
        for la, lb in zip(jax.tree_util.tree_leaves(a),
                          jax.tree_util.tree_leaves(b)):
            la, lb = np.asarray(la), np.asarray(lb)
            if la.ndim == 0:
                np.testing.assert_array_equal(la, lb)
            else:
                take = (np.take(la, rows, axis=axis),
                        np.take(lb, rows, axis=axis))
                np.testing.assert_array_equal(*take)

    def test_untouched_rows_bit_identical(self):
        state = self._advanced_state()
        rng = np.random.default_rng(10)
        spliced = refill_rows(state, [2], np.asarray(_mk_y(rng))[None], [True])
        others = [0, 1, 3]
        # batch-axis leaves: X, done, streak, Y, every leaf of `last`
        self._rows_equal(
            (state.X, state.done, state.streak, state.Y, state.last),
            (spliced.X, spliced.done, spliced.streak, spliced.Y, spliced.last),
            others)
        # trace buffers carry the batch on axis 1 (columns)
        self._rows_equal(state.trace, spliced.trace, others, axis=1)
        assert np.asarray(spliced.k) == np.asarray(state.k)
        # the spliced row is a fresh request row
        assert not bool(np.asarray(spliced.done)[2])
        assert np.all(np.asarray(spliced.X)[2] == 0)
        assert np.all(np.asarray(spliced.streak)[2] == 0)

    def test_untouched_rows_identical_through_next_segment(self):
        """Stronger: the splice must not perturb the other rows' *future*
        either — the next segment computes bitwise the same rows with or
        without the refill."""
        state = self._advanced_state()
        rng = np.random.default_rng(11)
        spliced = refill_rows(state, [2], np.asarray(_mk_y(rng))[None], [True])
        a = solver_segment(PHI, state, 4, s=S, early_exit=True)
        b = solver_segment(PHI, spliced, 4, s=S, early_exit=True)
        others = [0, 1, 3]
        self._rows_equal((a.X, a.done, a.streak), (b.X, b.done, b.streak),
                         others)
        self._rows_equal(a.trace, b.trace, others, axis=1)

    def test_pad_rows_are_done_and_zero(self):
        state = self._advanced_state()
        padded = refill_rows(state, [1, 3], np.zeros((2, M), np.float32),
                             [False, False])
        done = np.asarray(padded.done)
        assert bool(done[1]) and bool(done[3])
        assert np.all(np.asarray(padded.Y)[[1, 3]] == 0)

    def test_validation(self):
        state = self._advanced_state()
        with pytest.raises(ValueError, match="distinct"):
            refill_rows(state, [1, 1], np.zeros((2, M), np.float32),
                        [True, True])
        with pytest.raises(ValueError, match="out of range"):
            refill_rows(state, [7], np.zeros((1, M), np.float32), [True])
        with pytest.raises(ValueError, match="Y_rows shape"):
            refill_rows(state, [0], np.zeros((2, M), np.float32), [True])


class TestJournal:
    def test_scheduler_journals_request_identity(self, tmp_path):
        rng = np.random.default_rng(12)
        arrivals = _fuzz_trace(rng, 4)
        sch = ContinuousScheduler(PHI, S, N_ITERS, slots=2, seg_len=4,
                                  key=KEY, journal_dir=str(tmp_path))
        reports = sch.run(arrivals)
        j = ChunkJournal(str(tmp_path))
        for _, req in arrivals:
            assert j.is_complete(req.rid)
            Yj, _ = j.load_submit(req.rid)
            np.testing.assert_array_equal(Yj[0], np.asarray(req.y))
            import json
            with open(j._p(req.rid, "meta.json")) as f:
                meta = json.load(f)
            assert meta["rid"] == req.rid
            assert meta["priority"] == req.priority
            assert meta["n_iters"] == req.n_iters
            assert "arrival_tick" in meta
            np.testing.assert_array_equal(j.load_result_full(req.rid)[0],
                                          np.asarray(reports[req.rid].x))

    def test_scheduler_drains_on_resume(self, tmp_path):
        """A restarted scheduler fed the same trace serves every journaled
        result from disk — bitwise, zero segments run."""
        rng = np.random.default_rng(13)
        arrivals = _fuzz_trace(rng, 4)
        first = ContinuousScheduler(PHI, S, N_ITERS, slots=2, seg_len=4,
                                    key=KEY, journal_dir=str(tmp_path))
        before = first.run(arrivals)
        again = ContinuousScheduler(PHI, S, N_ITERS, slots=2, seg_len=4,
                                    key=KEY, journal_dir=str(tmp_path),
                                    resume=True)
        after = again.run(arrivals)
        assert again.segments_run == 0
        assert again.n_drained == len(arrivals)
        for _, req in arrivals:
            assert after[req.rid].drained
            np.testing.assert_array_equal(np.asarray(after[req.rid].x),
                                          np.asarray(before[req.rid].x))

    def test_resume_rejects_diverged_request(self, tmp_path):
        rng = np.random.default_rng(14)
        arrivals = _fuzz_trace(rng, 2)
        ContinuousScheduler(PHI, S, N_ITERS, slots=2, seg_len=4, key=KEY,
                            journal_dir=str(tmp_path)).run(arrivals)
        tick, req = arrivals[0]
        tampered = [(tick, Request(rid=req.rid, y=req.y + 1.0,
                                   priority=req.priority,
                                   n_iters=req.n_iters))] + arrivals[1:]
        sch = ContinuousScheduler(PHI, S, N_ITERS, slots=2, seg_len=4,
                                  key=KEY, journal_dir=str(tmp_path),
                                  resume=True)
        with pytest.raises(ValueError, match="journal mismatch"):
            sch.run(tampered)


class TestRowValidityMask:
    """BatchServer.submit / ChunkJournal row_mask: padded or harvested rows
    must never be journaled (or replayed) as user results."""

    def test_journal_contents_pinned(self, tmp_path):
        j = ChunkJournal(str(tmp_path))
        Y = np.arange(12, dtype=np.float32).reshape(3, 4)
        x = np.arange(15, dtype=np.float32).reshape(3, 5)
        mask = np.array([True, False, True])
        j.record_submit(0, Y, np.zeros(2, np.uint32), row_mask=mask)
        j.record_result(0, x, row_mask=mask)
        # on disk: the mask itself, rows_valid counts, and a COMPACTED x —
        # the invalid row's bytes are not in the journal at all
        np.testing.assert_array_equal(j.load_mask(0), mask)
        assert j.load_result(0).shape == (2, 5)
        np.testing.assert_array_equal(j.load_result(0), x[mask])
        full = j.load_result_full(0)
        assert full.shape == (3, 5)
        np.testing.assert_array_equal(full[mask], x[mask])
        assert np.all(full[1] == 0)
        import json
        with open(j._p(0, "meta.json")) as f:
            assert json.load(f)["rows_valid"] == 2
        with open(j._p(0, "done.json")) as f:
            done = json.load(f)
        assert done["b_total"] == 3 and done["rows_valid"] == 2

    def test_all_true_mask_is_canonical_none(self, tmp_path):
        """An explicit all-valid mask journals identically to no mask — one
        on-disk spelling per meaning, so pre-mask journals stay compatible."""
        j = ChunkJournal(str(tmp_path))
        Y = np.ones((2, 4), np.float32)
        j.record_submit(0, Y, np.zeros(2, np.uint32),
                        row_mask=np.array([True, True]))
        assert j.load_mask(0) is None
        j.verify_submit(0, Y, np.zeros(2, np.uint32))  # and vice versa

    def test_verify_submit_checks_mask(self, tmp_path):
        j = ChunkJournal(str(tmp_path))
        Y = np.ones((2, 4), np.float32)
        mask = np.array([True, False])
        j.record_submit(0, Y, np.zeros(2, np.uint32), row_mask=mask)
        j.verify_submit(0, Y, np.zeros(2, np.uint32), row_mask=mask)
        with pytest.raises(ValueError, match="mask"):
            j.verify_submit(0, Y, np.zeros(2, np.uint32))

    def test_batchserver_masked_submit(self, tmp_path):
        rng = np.random.default_rng(15)
        Y = jnp.stack([jnp.asarray(_mk_y(rng)) for _ in range(4)])
        mask = np.array([True, True, False, True])
        srv = BatchServer(PHI, S, N_ITERS, mesh=make_batch_mesh(1), key=KEY,
                          journal_dir=str(tmp_path))
        res = srv.submit(Y, KEY, row_mask=mask)
        # invalid rows are zeroed pre-solve and fix at x = 0
        assert np.all(np.asarray(res.x)[2] == 0)
        assert srv.n_items == 3          # masked rows are not served items
        j = ChunkJournal(str(tmp_path))
        np.testing.assert_array_equal(j.load_mask(0), mask)
        assert j.load_result(0).shape[0] == 3
        # drain on resume reconstructs the full shape bitwise
        srv2 = BatchServer(PHI, S, N_ITERS, mesh=make_batch_mesh(1), key=KEY,
                           journal_dir=str(tmp_path), resume=True)
        res2 = srv2.submit(Y, KEY, row_mask=mask)
        np.testing.assert_array_equal(np.asarray(res2.x), np.asarray(res.x))
        assert srv2.n_drained == 1


_MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax, jax.numpy as jnp
from repro.parallel import ContinuousScheduler, Request, make_batch_mesh

M, N, S = 16, 32, 3
rng = np.random.default_rng(42)
phi = jnp.asarray(rng.standard_normal((M, N)) / np.sqrt(M), jnp.float32)
def mk_y():
    x = np.zeros(N, np.float32)
    x[rng.choice(N, S, replace=False)] = rng.standard_normal(S).astype(np.float32)
    return np.asarray(phi) @ x
key = jax.random.PRNGKey(7)
arrivals = [(i // 2, Request(rid=i, y=mk_y(), priority=i % 2,
                             n_iters=[4, 8, 12][i % 3])) for i in range(8)]
# slots=8 on 4 devices: 2 rows per shard, so the sharded segment hits the
# batched-op path and parity stays bitwise (1 row/shard would be gemv)
sch = ContinuousScheduler(phi, S, 12, slots=8, seg_len=4, key=key,
                          mesh=make_batch_mesh(4))
reports = sch.run(arrivals)
for _, req in arrivals:
    ref = np.asarray(sch.reference_solve(req.y, req.n_iters))
    assert np.array_equal(ref, reports[req.rid].x), f"rid {req.rid} diverged"
print("MULTIDEV_SCHED_OK", len(arrivals))
"""


@pytest.mark.slow
def test_scheduler_multidevice_parity_subprocess():
    """Differential parity holds with the slot table sharded over 4 forced
    host devices (sharded_segment_run path)."""
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    # the forced host devices are CPU devices: never let the child reach for
    # an accelerator this process (or another) may hold
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", _MULTIDEV_SCRIPT], env=env,
                         cwd=root, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "MULTIDEV_SCHED_OK 8" in res.stdout
