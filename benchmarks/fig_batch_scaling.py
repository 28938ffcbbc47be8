"""Sharded batch serving: items/sec vs device count (BENCH_batch.json).

Measures the multi-device serving path (``qniht_batch_sharded`` /
``repro.parallel.batch``) on a forced multi-host-device CPU view: each device
count runs in a fresh subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the flag is only read
at backend initialization). Two workloads:

* **gaussian serve mix** — the heterogeneous stream of
  :mod:`repro.configs.serve_batch`: B = 64 rows against one (512, 1024) Φ,
  rows 0..7 a *burst* of hard items (geometrically decaying coefficients at
  15 dB — near-compressible, slow support resolution) and the rest clean flat
  s-sparse rows at 30 dB. ``n_iters = 96`` is the fixed serving horizon,
  provisioned for the hard rows; the per-row freeze rule (``exit_tol=1e-5``)
  is what makes the horizon cheap per item.
* **mri batch** — B = 8 randomized 64×64 brain phantoms through the
  matrix-free ``SubsampledFourierOperator`` (int8 observations), showing the
  sharded dispatch is operator-generic.

Comparisons recorded per device count (and asserted in the rows):

* ``baseline`` — the single-device ``qniht_batch`` path with its defaults
  (no early exit): pays the full horizon for every row. This is the
  pre-existing path a single-device deployment runs, and the denominator of
  ``speedup_vs_single_device``.
* ``sharded N`` — ``qniht_batch_sharded`` on an N-device ``batch`` mesh with
  the freeze rule. **Parity**: every sharded run is compared against the
  single-device path *with the same early-exit configuration* (the freeze
  rule is row-local, so results are invariant to the mesh width). Parity is
  bitwise whenever XLA's batched ops are batching-invariant at the problem
  shape — pinned on an 8-device mesh in tests/test_sharded_batch.py — and
  otherwise differs by ULP-level f32 accumulation (``max_dev_vs_singledev``
  records the worst element; the same hedge the ``qniht_batch`` ↔ ``qniht``
  row contract has always carried).

Scaling interpretation (honesty notes, also in docs/benchmarks.md): forced
host devices timeshare the container's physical cores (``host_cores`` in
every row), so fixed-work scaling is capped at ~#cores no matter the mesh
width — on this 2-core CI box the curve saturates around 2×. What the rows
demonstrate is the *structural* serving win that multiplies whatever
hardware curve a real mesh provides: per-shard early exit plus straggler
isolation (only the shard holding the hard burst pays the long tail, and the
fused single-device batch additionally pays the stragglers' backtracking on
every row's matmuls), against a per-shard cost floor set by the Φ stream
each shard re-reads (sharding de-amortizes the batch's operator traffic —
the paper's bandwidth law cuts both ways).

The device-count sweep is a CPU construct: it needs a child process per
forced host-device count, and on an accelerator the parent holds the chip the
children would need. On a non-CPU backend the sweep is refused with a message
and only the scheduling stage below runs.

A final single-device stage, in this process, compares **scheduling
policies** on the bursty single-request trace of ``serve-continuous``
(``repro.parallel.scheduler``):
``continuous`` (mid-flight slot refill) vs ``lockstep`` (full-table drains —
the chunked baseline in the same engine, same executable, same request set).
Rows carry p50/p99 request latency, items/sec, slot occupancy, and the
``speedup_vs_lockstep`` ratio; quality columns must match across policies
because every answer is bitwise its standalone solve (docs/serving.md).

Every run rewrites ``BENCH_batch.json`` (override via ``BENCH_BATCH_JSON``).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

JSON_PATH = os.environ.get("BENCH_BATCH_JSON", "BENCH_batch.json")
DEVICE_COUNTS = (1, 2, 4, 8)


def _best_wall(fn, reps):
    """Best-of-N wall time (the timeit convention: the minimum is the run
    least perturbed by scheduler noise — applied to every configuration
    equally)."""
    import jax

    jax.block_until_ready(fn())  # compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return min(ts)


def worker(ndev: int, fast: bool) -> None:
    """Runs inside the subprocess with the forced device count; prints one
    JSON line per measured row."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs.serve_batch import CONFIG
    from repro.core import qniht_batch, qniht_batch_sharded, relative_error
    from repro.launch.serve import build_stream
    from repro.sensing import make_mri_problem, brain_phantom, mri_observations

    reps = 5
    cfg = CONFIG
    tol = cfg.exit_tol
    key = jax.random.PRNGKey(cfg.seed)
    phi, chunks, truths = build_stream(dataclasses.replace(cfg, n_chunks=1), key)
    Y, X_true = chunks[0], truths[0]
    kw = dict(with_trace=False)

    rows = []
    if ndev == 1:
        w = _best_wall(lambda: qniht_batch(phi, Y, cfg.s, cfg.n_iters, **kw), reps)
        res = qniht_batch(phi, Y, cfg.s, cfg.n_iters, **kw)
        rel = [float(relative_error(res.x[b], X_true[b])) for b in range(cfg.chunk)]
        rows.append({
            "name": "batch/gaussian_B64_singledev_baseline", "devices": 1,
            "wall_ms": round(w * 1e3, 1), "items_per_s": round(cfg.chunk / w, 1),
            "rel_error_mean": round(sum(rel) / len(rel), 4),
        })

    w = _best_wall(
        lambda: qniht_batch_sharded(phi, Y, cfg.s, cfg.n_iters, n_devices=ndev,
                                    exit_tol=tol, **kw), reps)
    res = qniht_batch_sharded(phi, Y, cfg.s, cfg.n_iters, n_devices=ndev,
                              exit_tol=tol, **kw)
    # grouping-invariance: identical to the single-device path at the same
    # early-exit configuration, whatever the mesh width — bitwise when the
    # batched ops are batching-invariant at this shape, else ULP-level f32
    # accumulation differences (max_dev records the worst element)
    ref = qniht_batch(phi, Y, cfg.s, cfg.n_iters, early_exit=True, exit_tol=tol, **kw)
    rel = [float(relative_error(res.x[b], X_true[b])) for b in range(cfg.chunk)]
    rows.append({
        "name": f"batch/gaussian_B64_sharded_{ndev}dev", "devices": ndev,
        "wall_ms": round(w * 1e3, 1), "items_per_s": round(cfg.chunk / w, 1),
        "rel_error_mean": round(sum(rel) / len(rel), 4),
        "exit_tol": tol,
        "bitident_vs_singledev": bool(jnp.all(res.x == ref.x)),
        "max_dev_vs_singledev": float(jnp.max(jnp.abs(res.x - ref.x))),
    })

    # MRI: operator-generic sharding (matrix-free Fourier Φ, int8 k-space)
    r, B = 32 if fast else 64, 8
    prob = make_mri_problem(r, 4 * r, 0.4, key, snr_db=None)
    Img = jnp.stack([brain_phantom(r, jax.random.fold_in(key, b)).ravel()
                     for b in range(B)])
    from repro.sensing import sparsify_image
    Img = jnp.stack([sparsify_image(Img[b], 4 * r) for b in range(B)])
    Ym, _ = mri_observations(prob.op, Img, None, jax.random.fold_in(key, 99))
    w = _best_wall(
        lambda: qniht_batch_sharded(prob.op, Ym, 4 * r, 25, n_devices=ndev,
                                    bits_y=8, key=key, exit_tol=tol,
                                    real_signal=True, nonneg=True,
                                    with_trace=False), reps)
    res = qniht_batch_sharded(prob.op, Ym, 4 * r, 25, n_devices=ndev, bits_y=8,
                              key=key, exit_tol=tol, real_signal=True,
                              nonneg=True, with_trace=False)
    ref = qniht_batch(prob.op, Ym, 4 * r, 25, bits_y=8, key=key, early_exit=True,
                      exit_tol=tol, real_signal=True, nonneg=True, with_trace=False)
    rows.append({
        "name": f"batch/mri_{r}px_B8_sharded_{ndev}dev", "devices": ndev,
        "wall_ms": round(w * 1e3, 1), "items_per_s": round(B / w, 1),
        "exit_tol": tol,
        "bitident_vs_singledev": bool(jnp.all(res.x == ref.x)),
        "max_dev_vs_singledev": float(jnp.max(jnp.abs(res.x - ref.x))),
    })
    for row in rows:
        print("ROW " + json.dumps(row), flush=True)


def sched_rows(fast: bool) -> list[dict]:
    """Continuous vs lockstep scheduling on the bursty request trace
    (:mod:`repro.parallel.scheduler`), on this process's default device.

    Both policies run the SAME engine, executable, and request set — only the
    refill rule differs — so the items/sec ratio isolates the scheduling
    policy. Each policy runs twice and reports the second (warm) pass: the
    compile-once contract means a deployed scheduler pays tracing exactly
    once, and a cold wall would just measure XLA's compiler. Quality columns
    (rel error means) must match across policies: continuous reorders *when*
    rows run, never *what* they compute (every answer is bitwise its
    standalone solve — pinned by tests/test_scheduler.py and the ``sched`` CI
    tier, so this stage spends its wall on throughput, not re-verification).
    """
    import dataclasses

    from repro.configs.serve_batch import CONTINUOUS
    from repro.launch.serve import serve_scheduled

    cfg = (dataclasses.replace(CONTINUOUS, m=128, n=256, s=16, n_requests=48)
           if fast else CONTINUOUS)
    rows = []
    for policy in ("lockstep", "continuous"):
        serve_scheduled(cfg, policy)  # warm: trace + compile the segment step
        out = max((serve_scheduled(cfg, policy) for _ in range(3)),
                  key=lambda o: o["items_per_s"])  # best-of-N, timeit-style
        rows.append({
            "name": f"batch/continuous_sched_{policy}", "devices": 1,
            "wall_ms": round(out["wall_s"] * 1e3, 1),
            **{k: out[k] for k in (
                "scheduler", "requests", "completed", "slots", "seg_len",
                "segments_run", "slot_occupancy", "items_per_s",
                "latency_p50_s", "latency_p99_s", "queue_wait_ticks_mean",
                "iters_used_mean", "rel_error_easy_mean",
                "rel_error_hard_mean")},
        })
    lock = next(r for r in rows if r["scheduler"] == "lockstep")
    for r in rows:
        if r["scheduler"] == "continuous":
            r["speedup_vs_lockstep"] = round(
                r["items_per_s"] / lock["items_per_s"], 2)
    return rows


def _device_sweep(fast: bool) -> list[dict]:
    """One subprocess per forced host-device count (XLA_FLAGS is read once, at
    backend init, so each count needs a fresh process)."""
    from repro.parallel.batch import force_host_devices

    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(here)
    records = []
    for ndev in DEVICE_COUNTS:
        env = dict(os.environ)
        env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
        force_host_devices(ndev, env)
        cmd = [sys.executable, os.path.join(here, "fig_batch_scaling.py"),
               "--worker", str(ndev)] + (["--fast"] if fast else [])
        res = subprocess.run(cmd, env=env, cwd=root, capture_output=True,
                             text=True, timeout=1800)
        if res.returncode != 0:
            raise RuntimeError(
                f"scaling worker ndev={ndev} failed:\n{res.stderr[-2000:]}")
        for line in res.stdout.splitlines():
            if line.startswith("ROW "):
                records.append(json.loads(line[4:]))
    return records


def run(fast: bool = True):
    """Device-count sweep (CPU only), then the in-process scheduling stage.
    Yields CSV rows."""
    import jax

    backend = jax.default_backend()
    if backend == "cpu":
        records = _device_sweep(fast)
    else:
        print(f"# batch-scaling: device-count sweep refused on {backend!r}: it "
              "forces host (CPU) devices in child processes, and this process "
              "holds the accelerator they would need; run the sweep under "
              "JAX_PLATFORMS=cpu", flush=True)
        records = []
    records.extend(sched_rows(fast))

    base = next((r for r in records if r["name"].endswith("singledev_baseline")),
                None)
    out_rows = []
    for r in records:
        # the artifact must self-describe its hardware: forced host devices
        # timeshare the physical cores, which cap fixed-work scaling
        r["host_cores"] = os.cpu_count()
        if base and "gaussian" in r["name"] and "sharded" in r["name"]:
            r["speedup_vs_single_device"] = round(
                r["items_per_s"] / base["items_per_s"], 2)
        derived = " ".join(f"{k}={v}" for k, v in r.items()
                           if k not in ("name", "wall_ms"))
        out_rows.append(f"{r['name']},{r['wall_ms'] * 1e3:.1f},{derived}")

    from benchmarks.common import write_json

    write_json(records, JSON_PATH)
    yield from out_rows


if __name__ == "__main__":
    if "--worker" in sys.argv:
        i = sys.argv.index("--worker")
        worker(int(sys.argv[i + 1]), "--fast" in sys.argv)
    else:
        for row in run(fast="--full" not in sys.argv):
            print(row)
