"""One run of one cell: set up, warm up, measure, check, report.

The window drives the program's serving entry, ``BatchServer.submit``, in a
closed loop with one client: the next chunk is submitted only once the
previous answer is on the host. ``run_cell`` returns the result line as a
dict; ``bench/run.py`` prints it.
"""
from __future__ import annotations

import gc
import statistics
import sys
import time
from pathlib import Path

import jax
import numpy as np

from bench import check, spec, xplane

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COUNTERS: list = []
_LISTENING = False


class CompileCounter:
    """Backend compiles while active (JAX's compile monitoring event)."""

    def __init__(self):
        self.n = 0

    def __enter__(self):
        global _LISTENING
        if not _LISTENING:
            jax.monitoring.register_event_duration_secs_listener(_on_event)
            _LISTENING = True
        _COUNTERS.append(self)
        return self

    def __exit__(self, *exc):
        _COUNTERS.remove(self)


def _on_event(event, duration, **kw):
    if event == COMPILE_EVENT:
        for c in _COUNTERS:
            c.n += 1


def seed_keys(seed):
    """Independent keys for each use, from a seed of up to 64 bits."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)
    names = ("pool", "server", "obs", "warm")
    return {n: jax.random.fold_in(base, i + 1) for i, n in enumerate(names)}


def span(name):
    return jax.profiler.TraceAnnotation(name)


def device_check(chips, peaks):
    """The chips the cell runs on, or an error message."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return None, f"needs a TPU; JAX found {devs[0].platform!r}"
    if len(devs) < chips:
        return None, f"the cell asks for {chips} chips; JAX sees {len(devs)}"
    if devs[0].device_kind not in peaks:
        return None, f"device kind {devs[0].device_kind!r} is not in bench/peaks.json"
    return devs[:chips], None


def memory(devs):
    """(bytes in use, peak bytes in use) on the fullest device."""
    stats = [d.memory_stats() or {} for d in devs]
    return (max(s.get("bytes_in_use", 0) for s in stats),
            max(s.get("peak_bytes_in_use", 0) for s in stats))


def iterations_run(mu, changed, backtracks):
    """Iterations a shard ran under early exit, from its per-iteration rows
    (n_iters, b): the loop stops after the first iteration at which every row
    is at a fixed point, and every later row of the trace repeats that one."""
    rows = [np.nan_to_num(mu, nan=-1.0), changed.astype(np.float32),
            backtracks.astype(np.float32)]
    n = mu.shape[0]
    j = n - 1
    while j > 0 and all(np.array_equal(a[j - 1], a[n - 1]) for a in rows):
        j -= 1
    return min(n, j + 1)


def shard_counts(outs, shards):
    """Per chunk and shard: (iterations run, backtrack rounds in them)."""
    counts = []
    for mu, ch, bt in outs:
        mu, ch, bt = np.asarray(mu), np.asarray(ch), np.asarray(bt)
        b = mu.shape[1] // shards
        for d in range(shards):
            cols = slice(d * b, (d + 1) * b)
            it = iterations_run(mu[:, cols], ch[:, cols], bt[:, cols])
            counts.append((it, int(np.max(bt[:it, cols], axis=1).sum())))
    return counts


def run_cell(name, seed, seconds, trace, *, t_start, root=spec.ROOT,
             require_tpu=True, overrides=None, trace_dir=None, after_check=None,
             device_kind=None, log=sys.stderr):
    """One run of cell ``name``. Returns the result line (a dict), or None
    when the machine cannot run the cell.

    ``overrides`` replaces configuration and traffic keys (the CPU rehearsal
    runs the same path at small sizes, with ``device_kind`` naming the peaks
    its traced runs read); ``after_check(cell,
    chunks, ref, numbers)`` runs once the reference is in, and what it
    returns goes into the line under ``extra`` (the control readings)."""
    bench = spec.benchmark(root)
    wl = spec.workload(bench, name)
    cfg = spec.config(bench, wl["config"], root)
    tr = spec.traffic(wl["traffic"])
    for key, val in (overrides or {}).items():
        (tr if key in tr else cfg)[key] = val
    peaks = spec.peaks()
    if require_tpu:
        devs, err = device_check(wl["chips"], peaks)
        if err:
            print(f"[bench] {err}", file=log)
            return None
    else:
        devs = jax.devices()[:wl["chips"]]
    from repro.parallel.batch import make_batch_mesh

    keys = seed_keys(seed)
    mesh = make_batch_mesh(devices=devs)
    cell = spec.problem(cfg["problem"]).Cell(cfg, tr, keys, mesh, span)
    request = cell.request
    obs_key = lambda i: jax.random.fold_in(keys["obs"], i)  # noqa: E731

    # warm-up: the window's own calls, on keys the window never uses
    warm = []
    for j in range(2):
        t0 = time.monotonic()
        request(j, jax.random.fold_in(keys["warm"], j))
        obs_key(j)
        warm.append(time.monotonic() - t0)
    setup_s = time.monotonic() - t_start
    print(f"[bench] set-up {setup_s:.3f} s: " + ", ".join(
        f"{k} {v:.3f} s" for k, v in cell.times.items())
        + f", warm-up chunks {warm[0]:.3f} s and {warm[1]:.3f} s", file=log)

    trace_path = Path(trace_dir) if trace else None
    answers, outs, lat = {}, [], []
    n_traced = 0   # chunks that ran while the profiler was on
    with CompileCounter() as compiles:
        tracing = _start_trace(trace_path) if trace else None
        t_begin = time.perf_counter()
        t_end = t_begin
        i = 0
        while t_end - t_begin < seconds:
            t0 = time.perf_counter()
            x, counts = request(i, obs_key(i))
            t_end = time.perf_counter()
            lat.append(t_end - t0)
            answers[i] = x
            outs.append(counts)
            i += 1
            if tracing and t_end - t_begin >= tr["trace_seconds"]:
                tracing = _stop_trace(tracing)
                n_traced = i
        if tracing:
            tracing = _stop_trace(tracing)
            n_traced = i
    n_chunks = len(lat)
    rows = tr["chunk_rows"]
    window_s = t_end - t_begin

    shards = mesh.devices.size
    counts = shard_counts(outs, shards)
    traced_counts = counts[:n_traced * shards]
    del outs
    cell.drop_pool()
    gc.collect()
    resident, peak = memory(devs)
    cell.free()
    gc.collect()

    # correctness: a sample of the window's answers, drawn from the seed
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    picks = sorted(rng.choice(n_chunks, size=min(tr["check_chunks"], n_chunks),
                              replace=False).tolist())
    chunks = [(i, obs_key(i)) for i in picks]
    got = np.concatenate([answers[i] for i in picks])
    del answers
    t_ref = time.perf_counter()
    ref = cell.reference(chunks)
    ref_s = time.perf_counter() - t_ref
    numbers = check.compare(got, ref)
    verdict = check.judge(numbers, cfg["limits"])
    extra = after_check(cell, chunks, ref, numbers) if after_check else None

    print(f"[bench] {name} seed={seed}: {n_chunks} chunks of {rows} rows in "
          f"{window_s:.3f} s; request samples={n_chunks}; compiles in window="
          f"{compiles.n}; reference over {len(picks)} chunks took {ref_s:.2f} s",
          file=log)
    line = {
        "correct": verdict["correct"],
        "attempted": n_chunks * rows,
        "failed": 0,
        "metrics": {},
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": int(peak)},
    }
    if not trace:
        e2e = {"recoveries_per_s": n_chunks * rows / window_s,
               "request_p95_s": _p95(lat), "resident_mb": resident / 1e6,
               "setup_s": setup_s}
        for m in bench["end_to_end"]:
            if spec.applies(m, name):
                line["metrics"][m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        red = xplane.reduce(xplane.load(trace_path), len(devs))
        line["device"]["busy_s"] = red["busy_s"]
        line["device"]["window_s"] = red["window_s"]
        line["breakdown"] = red["breakdown"]
        ctx = {"trace": red, "counts": counts, "traced_counts": traced_counts,
               "compiles": compiles.n,
               "cfg": cfg, "traffic": tr, "rows": rows, "shards": len(devs),
               "peak": peaks[device_kind or devs[0].device_kind], "notes": []}
        for m in bench["per_layer"]:
            if spec.applies(m, name):
                val = spec.reader(m["name"])(ctx)
                if val is not None:
                    line["metrics"][m["name"]] = {"value": val, "unit": m["unit"]}
        for note in ctx["notes"]:
            print(f"[bench] {note}", file=log)
    for c in verdict["checks"]:
        print(f"[bench] check {c['name']}={c['value']} limit={c['limit']} "
              f"({'ok' if c['ok'] else 'FAIL'})", file=log)
    if extra is not None:
        line["extra"] = extra
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in verdict["checks"]}
    return line


def _start_trace(path):
    """Profile the device and the harness's spans; no Python tracer."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(path), profiler_options=opts)
    win = span("bench.window")
    win.__enter__()
    return win


def _stop_trace(win):
    win.__exit__(None, None, None)
    jax.profiler.stop_trace()
    return None


def _p95(values):
    """95th percentile (inclusive method) of all request times."""
    if len(values) < 2:
        return values[0]
    return statistics.quantiles(values, n=20, method="inclusive")[18]

