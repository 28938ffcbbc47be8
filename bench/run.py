#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload lofar-cs302-int8.live-b8 --seed 7 \
        --seconds 30 --trace 0

Loads the cell named in ``BENCHMARK.json``, builds its inputs and the
program's server from ``--seed``, warms up every program the window uses
(that is set-up), then measures for ``--seconds``. With ``--trace 0`` it
reports the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from a profiler trace of the window. After the window it
checks a sample of the answers against the plain reference; the numbers
compared are the last lines on standard error and the ``checks`` key of the
result. The last line of standard output is the result, one JSON object.

Exits non-zero, printing no result, when JAX finds no TPU, fewer chips than
the cell asks for, or a device kind missing from ``bench/peaks.json``.
JAX's persistent compilation cache lives in ``.jax_cache/`` at the root of
the checkout, so only the first run of a cell there compiles.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True, help="a cell of BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="where the profiler writes (default .bench_traces/<cell>)")
    args = ap.parse_args(argv)

    # the checkout's own cache directory, fixed, set before JAX reads it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from bench import harness

    trace_dir = Path(args.trace_dir or ROOT / ".bench_traces" / args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    line = harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                            t_start=T_START, trace_dir=trace_dir)
    if line is None:
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
