"""Sharded batch-recovery serving driver — the heavy-traffic loop as a CLI.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.serve --devices 8 --chunks 4

Simulates the production shape of the system: a stream of fixed-size (B, M)
observation chunks arriving against ONE measurement operator, recovered by a
:class:`repro.parallel.batch.BatchServer` whose ``batch`` mesh splits each
chunk's rows across devices. The driver demonstrates the three amortizations
the serving mode is built around:

* the operator is packed once at server construction (``--config
  serve-gaussian-packed``) — chunk programs stream codes, never re-quantize;
* the sharded solve compiles once per (chunk shape, solver config) and every
  later chunk reuses the executable (the driver prints compile vs steady-state
  wall times);
* per-shard ``early_exit`` lets shards of converged rows stop iterating while
  the shard holding the workload's hard rows keeps going.

``--scheduler continuous|lockstep`` switches from pre-cut chunks to the
continuous-batching scheduler (:mod:`repro.parallel.scheduler`) over a bursty
single-request arrival trace (``--config serve-continuous*``): an admission
queue with priorities/deadlines feeds a slot table whose finished rows are
harvested and refilled mid-flight (``continuous``) or only at full-table
drains (``lockstep`` — the chunked baseline in the same engine). ``--verify``
asserts every answer bitwise against its standalone solve; ``--metrics-json``
publishes the p50/p99 latency + occupancy metrics. See ``docs/serving.md``.

The default workload is the heterogeneous stream of
:mod:`repro.configs.serve_batch` (a leading burst of low-SNR rows per chunk);
``--devices N`` picks the mesh width. On CPU the flag above must force the
multi-device view before jax initializes — the driver sets it for you when
run as ``__main__`` with ``--devices`` (it exports XLA_FLAGS before the first
jax call). Scaling numbers live in ``benchmarks/fig_batch_scaling.py`` /
``BENCH_batch.json``; see ``docs/benchmarks.md``.
"""
from __future__ import annotations

import argparse
import time


def build_stream(cfg, key):
    """(phi, chunks, truths): ``cfg.n_chunks`` chunks of ``cfg.chunk`` rows
    sharing one Φ. Rows 0..n_hard-1 of each chunk are the *hard burst* —
    geometrically decaying coefficients (``cfg.hard_decay``) observed at
    ``snr_hard_db`` — and the rest flat s-sparse rows at ``snr_easy_db``."""
    import jax
    import jax.numpy as jnp

    from repro.sensing import make_gaussian_problem

    base = make_gaussian_problem(cfg.m, cfg.n, cfg.s, None, key)

    def sig(k, decay):
        perm = jax.random.permutation(k, cfg.n)[: cfg.s]
        amps = jnp.power(decay, jnp.arange(cfg.s, dtype=jnp.float32))
        signs = jax.random.rademacher(jax.random.fold_in(k, 1), (cfg.s,), jnp.float32)
        return jnp.zeros(cfg.n).at[perm].set(amps * signs)

    def obs(x, snr, k):
        y = x @ base.phi.T
        noise = jax.random.normal(k, y.shape) * jnp.sqrt(
            jnp.mean(y**2) / 10 ** (snr / 10))
        return y + noise

    chunks, truths = [], []
    for ci in range(cfg.n_chunks):
        ys, xs = [], []
        for b in range(cfg.chunk):
            kb = jax.random.fold_in(key, 1 + ci * cfg.chunk + b)
            decay, snr = ((cfg.hard_decay, cfg.snr_hard_db) if b < cfg.n_hard
                          else (1.0, cfg.snr_easy_db))
            x = sig(kb, decay)
            xs.append(x)
            ys.append(obs(x, snr, jax.random.fold_in(kb, 9)))
        chunks.append(jnp.stack(ys))
        truths.append(jnp.stack(xs))
    return base.phi, chunks, truths


def build_requests(cfg, key):
    """(phi, arrivals, truths, hard_rids) for a
    :class:`~repro.configs.serve_batch.ContinuousServeConfig`: single-request
    arrivals on a deterministic bursty Poisson clock.

    Arrival ticks come from a ``numpy`` generator seeded by ``cfg.seed``
    (Poisson(``arrival_rate``) per tick plus a ``burst_size`` burst every
    ``burst_every`` ticks); request contents reuse the hard/easy recipe of
    :func:`build_stream` (request ``rid`` plays the role of the chunk-row
    index, so the same fold_in keys generate the same signals). Priorities
    are round-robin over ``cfg.priority_classes`` (0 = most urgent) and
    deadlines follow ``cfg.deadline_slack`` (None = no deadlines).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.parallel.scheduler import Request
    from repro.sensing import make_gaussian_problem

    base = make_gaussian_problem(cfg.m, cfg.n, cfg.s, None, key)

    def sig(k, decay):
        perm = jax.random.permutation(k, cfg.n)[: cfg.s]
        amps = jnp.power(decay, jnp.arange(cfg.s, dtype=jnp.float32))
        signs = jax.random.rademacher(jax.random.fold_in(k, 1), (cfg.s,), jnp.float32)
        return jnp.zeros(cfg.n).at[perm].set(amps * signs)

    def obs(x, snr, k):
        y = x @ base.phi.T
        noise = jax.random.normal(k, y.shape) * jnp.sqrt(
            jnp.mean(y**2) / 10 ** (snr / 10))
        return y + noise

    rng = np.random.default_rng(cfg.seed)
    hard_stride = max(1, int(round(1.0 / cfg.hard_fraction))) if cfg.hard_fraction > 0 else 0
    arrivals, truths, hard_rids = [], {}, set()
    rid, tick = 0, 0
    while rid < cfg.n_requests:
        n_new = int(rng.poisson(cfg.arrival_rate))
        if cfg.burst_every and tick and tick % cfg.burst_every == 0:
            n_new += cfg.burst_size
        for _ in range(min(n_new, cfg.n_requests - rid)):
            hard = hard_stride and rid % hard_stride == 0
            kb = jax.random.fold_in(key, 1 + rid)
            decay, snr = ((cfg.hard_decay, cfg.snr_hard_db) if hard
                          else (1.0, cfg.snr_easy_db))
            x = sig(kb, decay)
            y = obs(x, snr, jax.random.fold_in(kb, 9))
            prio = rid % cfg.priority_classes
            deadline = (None if cfg.deadline_slack is None
                        else tick + cfg.deadline_slack * (prio + 1))
            budget = (cfg.n_iters if hard or cfg.n_iters_easy is None
                      else cfg.n_iters_easy)
            arrivals.append((tick, Request(rid=rid, y=np.asarray(y),
                                           priority=prio, deadline=deadline,
                                           n_iters=budget)))
            truths[rid] = x
            if hard:
                hard_rids.add(rid)
            rid += 1
        tick += 1
    return base.phi, arrivals, truths, hard_rids


def serve_scheduled(cfg, policy, devices=None, journal_dir=None, resume=False,
                    sanitize=None, verify=False):
    """Run the bursty request trace through a
    :class:`~repro.parallel.scheduler.ContinuousScheduler`; returns metrics.

    ``policy`` is ``"continuous"`` (mid-flight slot refill) or ``"lockstep"``
    (refill only when every slot is free — the chunked baseline in the same
    engine). The metrics dict carries the latency-observability fields the
    benchmark plots: p50/p99 request latency, items/sec, slot occupancy,
    queue-wait and iters-used means, and shed counts.

    ``verify=True`` recomputes every completed request's standalone reference
    (:meth:`~repro.parallel.scheduler.ContinuousScheduler.reference_solve`),
    records whether every answer matched it bitwise and the largest absolute
    deviation (``verify_bitwise``, ``verify_max_abs_dev``), and raises unless
    every answer is bitwise equal — the differential contract as a CLI flag
    (the ``sched`` CI tier runs it on the smoke config).

    ``journal_dir``/``resume`` journal each request under its rid at splice
    time and drain completed results on restart, exactly like the chunked
    path (``metrics["drained"]`` counts requests served from disk).
    """
    import contextlib
    import statistics

    import jax
    import numpy as np

    from repro.core import relative_error
    from repro.parallel import ContinuousScheduler, make_batch_mesh

    if sanitize is None:
        sanitize = getattr(cfg, "sanitize", False)
    key = jax.random.PRNGKey(cfg.seed)
    phi, arrivals, truths, hard_rids = build_requests(cfg, key)
    kw = {}
    if cfg.backend == "packed":
        kw = dict(bits_phi=cfg.bits_phi, bits_y=cfg.bits_y, backend="packed")
    elif cfg.bits_y:
        kw = dict(bits_y=cfg.bits_y)
    if sanitize:
        # same contract as the chunked path: NaN trace markers would trip
        # debug_nans, so sanitized runs pay for the real residual trace
        kw["with_trace"] = True
        from repro.analysis.sanitize import sanitize as sanitize_ctx

        ctx = sanitize_ctx()
    else:
        ctx = contextlib.nullcontext()

    counter = None
    t0 = time.time()
    with ctx as counter:
        sch = ContinuousScheduler(
            phi, cfg.s, cfg.n_iters, slots=cfg.slots, seg_len=cfg.seg_len,
            policy=policy, queue_depth=cfg.queue_depth,
            age_every=cfg.age_every, mesh=make_batch_mesh(devices) if devices else None,
            key=key, exit_tol=cfg.exit_tol, journal_dir=journal_dir,
            resume=resume, **kw)
        reports = sch.run(arrivals)
        if counter is not None:
            counter.mark_warm()
    wall = time.time() - t0
    if counter is not None:
        print(f"[sanitize] ok {counter.summary()} debug_nans=on debug_infs=on",
              flush=True)

    done = [r for r in reports.values() if r.status == "done"]
    verify_fields = {}
    if verify:
        bitwise, max_dev, worst = True, 0.0, None
        for r in done:
            _, req = next(a for a in arrivals if a[1].rid == r.rid)
            ref = np.asarray(sch.reference_solve(req.y, req.n_iters))
            x = np.asarray(r.x)
            bitwise &= bool(np.array_equal(ref, x))
            dev = float(np.max(np.abs(ref - x)))
            if dev > max_dev:
                max_dev, worst = dev, r.rid
        print(f"[serve] verified {len(done)} requests against standalone "
              f"solves: bitwise={bitwise} max_abs_dev={max_dev}", flush=True)
        if not bitwise:
            raise AssertionError(
                f"request {worst}: scheduler answer deviates from its "
                f"standalone reference solve by up to {max_dev}")
        verify_fields = {"verify_bitwise": bitwise, "verify_max_abs_dev": max_dev}
    lat = sorted(r.latency_s for r in done)
    waits = [r.queue_wait_ticks for r in done if r.queue_wait_ticks is not None]
    iters = [r.iters_used for r in done if r.iters_used is not None]
    rels_easy = [float(relative_error(np.asarray(r.x), truths[r.rid]))
                 for r in done if r.rid not in hard_rids]
    rels_hard = [float(relative_error(np.asarray(r.x), truths[r.rid]))
                 for r in done if r.rid in hard_rids]
    stats = sch.stats()

    def pct(xs, q):
        if not xs:
            return None
        return round(xs[min(len(xs) - 1, int(q * (len(xs) - 1) + 0.999999))], 4)

    sanitize_fields = {} if counter is None else {
        "sanitize_compiles": counter.compiles,
    }
    return {
        **sanitize_fields,
        **verify_fields,
        "scheduler": policy,
        "requests": len(reports),
        "completed": len(done),
        "drained": stats["drained"],
        "shed_deadline": stats.get("n_shed_deadline", 0),
        "shed_queue_full": stats.get("n_shed_queue_full", 0),
        "slots": cfg.slots,
        "seg_len": cfg.seg_len,
        "ticks": stats["ticks"],
        "segments_run": stats["segments_run"],
        "segment_lengths": stats["segment_lengths"],
        "slot_occupancy": stats["slot_occupancy"],
        "wall_s": round(wall, 3),
        "items_per_s": round(len(done) / wall, 1) if wall else None,
        "latency_p50_s": pct(lat, 0.50),
        "latency_p99_s": pct(lat, 0.99),
        "queue_wait_ticks_mean": (round(statistics.mean(waits), 2)
                                  if waits else None),
        "iters_used_mean": round(statistics.mean(iters), 1) if iters else None,
        "rel_error_easy_mean": (round(sum(rels_easy) / len(rels_easy), 4)
                                if rels_easy else None),
        "rel_error_hard_mean": (round(sum(rels_hard) / len(rels_hard), 4)
                                if rels_hard else None),
    }


def serve(cfg, devices=None, chunks=None, journal_dir=None, resume=False,
          sanitize=None, profile_dir=None):
    """Run the stream through a BatchServer; returns a metrics dict.

    With ``journal_dir``, each chunk is write-ahead journaled and the loop
    runs under a :class:`~repro.train.fault.PreemptionGuard`: a SIGTERM/SIGINT
    finishes (and journals) the in-flight chunk, then stops cleanly. A
    restarted run with ``resume=True`` re-presents the same deterministic
    stream, drains journaled results and solves the rest — the per-chunk
    ``x_digest`` lines it prints are bit-identical to an uninterrupted run's
    (the fault-injection tests assert exactly that).

    ``profile_dir`` captures a JAX profiler trace of the whole serving loop
    (compile chunk included — filter by the steady-state chunks when reading;
    see docs/performance.md).

    ``sanitize`` (default: ``cfg.sanitize``) runs the whole loop under
    :func:`repro.analysis.sanitize.sanitize`: any NaN/Inf anywhere raises at
    the producing op, and a compile counter is marked warm after the first
    chunk — the ``[sanitize]`` summary line and the ``compiles*`` metrics
    fields report whether the compile-once contract held.
    """
    import contextlib
    import hashlib

    import jax
    import numpy as np

    from repro.core import relative_error
    from repro.parallel import BatchServer, make_batch_mesh
    from repro.train.fault import PreemptionGuard

    if sanitize is None:
        sanitize = getattr(cfg, "sanitize", False)
    key = jax.random.PRNGKey(cfg.seed)
    if chunks is not None:
        cfg = __import__("dataclasses").replace(cfg, n_chunks=chunks)
    phi, stream, truths = build_stream(cfg, key)
    mesh = make_batch_mesh(devices)
    kw = {}
    if cfg.backend == "packed":
        kw = dict(bits_phi=cfg.bits_phi, bits_y=cfg.bits_y, backend="packed")
    elif cfg.bits_y:
        kw = dict(bits_y=cfg.bits_y)
    if sanitize:
        # with_trace=False fills the trace outputs with NaN markers, which
        # debug_nans would (correctly) refuse — sanitized runs pay for the
        # real residual trace instead
        kw["with_trace"] = True
        from repro.analysis.sanitize import sanitize as sanitize_ctx

        ctx = sanitize_ctx()
    else:
        ctx = contextlib.nullcontext()

    prof = (jax.profiler.trace(profile_dir) if profile_dir
            else contextlib.nullcontext())
    walls, rels_easy, rels_hard = [], [], []
    preempted = None
    counter = None
    with prof, ctx as counter, PreemptionGuard() as guard:
        srv = BatchServer(phi, cfg.s, cfg.n_iters, mesh=mesh, key=key,
                          exit_tol=cfg.exit_tol, journal_dir=journal_dir,
                          resume=resume, **kw)
        for ci, Y in enumerate(stream):
            t0 = time.time()
            res = srv.submit(Y, jax.random.fold_in(key, 1000 + ci))
            jax.block_until_ready(res.x)
            walls.append(time.time() - t0)
            digest = hashlib.sha256(np.asarray(res.x).tobytes()).hexdigest()[:16]
            print(f"[serve] chunk {ci} x_digest={digest}", flush=True)
            for b in range(cfg.chunk):
                rel = float(relative_error(res.x[b], truths[ci][b]))
                (rels_hard if b < cfg.n_hard else rels_easy).append(rel)
            if counter is not None and ci == 0:
                # warm-up = chunk 0 end to end, metrics included: later
                # chunks must reuse both the sharded solve executable and
                # the small eager metric programs
                counter.mark_warm()
            if guard.requested and ci + 1 < len(stream):
                preempted = ci + 1
                print(f"[serve] preempted after chunk {ci} "
                      f"(journal has {ci + 1}/{len(stream)} chunks)", flush=True)
                break
    if counter is not None:
        print(f"[sanitize] ok {counter.summary()} debug_nans=on debug_infs=on",
              flush=True)
    steady = walls[1:] if len(walls) > 1 else walls
    items_per_s = cfg.chunk / (sum(steady) / len(steady))
    sanitize_fields = {} if counter is None else {
        "sanitize_compiles": counter.compiles,
        "sanitize_compiles_after_warmup": counter.compiles_since_warm,
    }
    return {
        **sanitize_fields,
        "devices": srv.n_shards,
        "chunks": len(stream),
        "chunks_served": srv.n_chunks,
        "chunks_drained": srv.n_drained,
        "preempted_after": preempted,
        "chunk_rows": cfg.chunk,
        "compile_chunk_s": round(walls[0], 3),
        "steady_chunk_s": round(sum(steady) / len(steady), 3),
        "items_per_s": round(items_per_s, 1),
        "rel_error_easy_mean": round(sum(rels_easy) / len(rels_easy), 4),
        "rel_error_hard_mean": (round(sum(rels_hard) / len(rels_hard), 4)
                                if rels_hard else None),
        "compile_cache_keys": list(map(list, srv.compile_cache_keys)),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--config", default="serve-gaussian-smoke",
                    choices=["serve-gaussian", "serve-gaussian-packed",
                             "serve-gaussian-smoke", "serve-gaussian-fault",
                             "serve-gaussian-fault-packed", "serve-continuous",
                             "serve-continuous-packed",
                             "serve-continuous-smoke"])
    ap.add_argument("--scheduler", default="chunked",
                    choices=["chunked", "continuous", "lockstep"],
                    help="chunked = the BatchServer loop over pre-cut chunks "
                         "(ServeConfig); continuous|lockstep = the "
                         "ContinuousScheduler over the bursty request trace "
                         "(ContinuousServeConfig) with mid-flight refill on "
                         "or off")
    ap.add_argument("--metrics-json", default=None,
                    help="also write the metrics dict to this path as JSON "
                         "(atomic publish)")
    ap.add_argument("--verify", action="store_true",
                    help="(scheduler modes) recompute every completed "
                         "request's standalone qniht_batch reference and "
                         "assert bitwise equality")
    ap.add_argument("--devices", type=int, default=None,
                    help="mesh width (default: all visible devices); on CPU "
                         "also forces that many host devices when set before "
                         "jax initializes")
    ap.add_argument("--chunks", type=int, default=None,
                    help="override the config's number of stream chunks")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="write-ahead journal directory: each chunk's inputs "
                         "are journaled before its solve and the result after, "
                         "and SIGTERM/SIGINT stops cleanly at a chunk boundary")
    ap.add_argument("--resume", action="store_true",
                    help="drain already-journaled chunk results from "
                         "--checkpoint-dir instead of re-solving them")
    ap.add_argument("--sanitize", action="store_true", default=None,
                    help="run under repro.analysis.sanitize: raise on any "
                         "NaN/Inf and report backend compiles after warm-up "
                         "(default: the config's sanitize flag)")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a JAX profiler trace of the serving loop "
                         "under this directory (see docs/performance.md)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.chunks is not None and args.chunks < 1:
        ap.error("--chunks must be >= 1")
    if args.resume and not args.checkpoint_dir:
        ap.error("--resume needs --checkpoint-dir")

    if args.devices:
        # must happen before the first jax call in this process
        from repro.parallel.batch import force_host_devices

        force_host_devices(args.devices)

    from repro.configs.serve_batch import (
        CONFIG, CONTINUOUS, CONTINUOUS_PACKED, CONTINUOUS_SMOKE, FAULT,
        FAULT_PACKED, PACKED, SMOKE)

    cfg = {"serve-gaussian": CONFIG, "serve-gaussian-packed": PACKED,
           "serve-gaussian-smoke": SMOKE, "serve-gaussian-fault": FAULT,
           "serve-gaussian-fault-packed": FAULT_PACKED,
           "serve-continuous": CONTINUOUS,
           "serve-continuous-packed": CONTINUOUS_PACKED,
           "serve-continuous-smoke": CONTINUOUS_SMOKE}[args.config]
    is_continuous_cfg = args.config.startswith("serve-continuous")
    if (args.scheduler != "chunked") != is_continuous_cfg:
        ap.error("--scheduler continuous|lockstep goes with the "
                 "serve-continuous* configs; chunked with the serve-gaussian* "
                 "ones")
    if args.scheduler == "chunked":
        out = serve(cfg, args.devices, args.chunks,
                    journal_dir=args.checkpoint_dir, resume=args.resume,
                    sanitize=args.sanitize, profile_dir=args.profile_dir)
    else:
        if args.profile_dir:
            ap.error("--profile-dir is a chunked-path flag")
        out = serve_scheduled(cfg, args.scheduler, devices=args.devices,
                              journal_dir=args.checkpoint_dir,
                              resume=args.resume, sanitize=args.sanitize,
                              verify=args.verify)
    if args.metrics_json:
        from repro.parallel.journal import write_json_durable

        write_json_durable(args.metrics_json, out)
    print(f"[serve] {cfg.name}: " +
          " ".join(f"{k}={v}" for k, v in out.items()))


if __name__ == "__main__":
    main()
