#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's numbers and the
control's, on several seeds in one process.

    python3 bench/control.py --workload lofar-cs302-int8.live-b8 \
        --seeds 101,102,103 --seconds 3

For each seed it runs the cell as ``bench/run.py`` does (a short window at
the cell's own load), compares the sampled answers with the plain reference,
and then puts each control in the program's place: the reference computed in
a precision below one the configuration states (its ``controls`` entry,
by name: fewer bits of the codes, or products of fewer bfloat16 passes).
It prints one JSON line per seed, and a summary: the largest reading of
each number over the program's runs (the lower reading of a limit) and the
smallest over each control's (the upper reading). ``--fault <name>`` breaks
the answers where the server produces them instead (``answer``: every row;
``slot0``: the first row of every chunk); its readings are the program's
numbers. The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _answer(x):
    """Every answer altered where it is produced: the image moved a pixel."""
    import jax.numpy as jnp

    return jnp.roll(x, 1, axis=-1)


def _slot0(x):
    """Only the first row of each chunk altered: a fault confined to one slot
    (padding, a row mask, one row's early exit)."""
    import jax.numpy as jnp

    return x.at[0].set(jnp.roll(x[0], 1, axis=-1))


FAULTS = {"answer": _answer, "slot0": _slot0}


def plant(fault):
    """Break ``BatchServer.submit`` with ``fault``; returns the undo."""
    from repro.core.niht import IHTResult
    from repro.parallel.batch import BatchServer

    submit = BatchServer.submit

    def broken(self, Y, key=None, row_mask=None):
        res = submit(self, Y, key=key, row_mask=row_mask)
        return IHTResult(x=FAULTS[fault](res.x), trace=res.trace)

    BatchServer.submit = broken
    return lambda: setattr(BatchServer, "submit", submit)


def control_numbers(cell, chunks, ref, numbers):
    from bench import check

    return {"program": numbers,
            "control": {name: check.compare(cell.reference(chunks, **kw), ref)
                        for name, kw in cell.cfg["controls"].items()}}


def program_numbers(cell, chunks, ref, numbers):
    return {"program": numbers}


def readings(name, seeds, seconds, *, fault=None, log=sys.stderr, **kw):
    """Per seed: the program's numbers and the control's (``kw`` goes to
    ``harness.run_cell``)."""
    from bench import harness

    undo = plant(fault) if fault else None
    try:
        out = []
        for seed in seeds:
            line = harness.run_cell(
                name, seed, seconds, False, t_start=time.monotonic(), log=log,
                after_check=program_numbers if fault else control_numbers, **kw)
            if line is None:
                return None
            extra = line.get("extra") or {}
            out.append({"seed": seed, "correct": line["correct"],
                        "program": extra.get("program"),
                        "control": extra.get("control"),
                        "checks": line["checks"]})
        return out
    finally:
        if undo:
            undo()


def _pick(got, pick):
    return {k: pick(g[k] for g in got) for k in got[0]}


def summary(rows):
    """Largest program reading and smallest reading of each control, of
    each number."""
    out = {"program": _pick([r["program"] for r in rows], max)}
    ctl = [r["control"] for r in rows if r.get("control")]
    if ctl:
        out["control"] = {name: _pick([c[name] for c in ctl], min) for name in ctl[0]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--fault", choices=sorted(FAULTS), default=None)
    args = ap.parse_args(argv)

    # the checkout's own cache directory, fixed, set before JAX reads it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    rows = readings(args.workload, [int(s) for s in args.seeds.split(",")],
                    args.seconds, fault=args.fault)
    if rows is None:
        return 2
    for r in rows:
        print(json.dumps(r), flush=True)
    print(json.dumps({"summary": summary(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
