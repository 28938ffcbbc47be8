"""The comparison that decides ``correct``: the program's answers against the
plain reference's, row by row.

Each answer is a recovered image (a sky, or a slice's wavelet coefficients).
Its gap is ||x_program - x_reference|| / ||x_reference||. Two runs of the same
algorithm on the same codes differ by rounding, and where rounding flips one
support decision the two trajectories part, so the numbers compared are
statistics over the sampled rows, each held to its own limit:

* ``median_gap`` -- the median row gap;
* ``rows_off`` -- the share of rows whose gap exceeds ``OFF_GAP``;
* ``nonfinite`` -- rows of the program with a NaN or an infinity (limit 0).
"""
from __future__ import annotations

import numpy as np

OFF_GAP = 1e-2


def compare(got, ref):
    """Numbers of the program's answers ``got`` against ``ref`` (rows x N)."""
    got = np.asarray(got, np.float64).reshape(ref.shape[0], -1)
    ref = np.asarray(ref, np.float64).reshape(ref.shape[0], -1)
    finite = np.isfinite(got).all(axis=1)
    den = np.maximum(np.linalg.norm(ref, axis=1), np.finfo(np.float32).tiny)
    gap = np.where(finite, np.linalg.norm(np.nan_to_num(got - ref), axis=1) / den,
                   np.inf)
    return {
        "median_gap": float(np.median(gap)),
        "rows_off": float(np.mean(gap > OFF_GAP)),
        "nonfinite": int(np.sum(~finite)),
        "max_gap": float(np.max(gap)),
        "rows": int(ref.shape[0]),
    }


def judge(numbers, limits):
    """Each limited number against its limit; ``correct`` when all hold."""
    checks = []
    for name in ("nonfinite",) + tuple(limits):
        limit = limits.get(name, 0)
        val = numbers[name]
        checks.append({"name": name, "value": val, "limit": limit,
                       "ok": bool(np.isfinite(val) and val <= limit)})
    return {"correct": all(c["ok"] for c in checks), "checks": checks}
