"""The operator work the algorithm needs, counted from the configuration and
from the iterations the solver ran, for the kernels' roofline shares.

An NIHT iteration applies the operator four times before any backtrack round
(Phi x for the residual, Phi^H r for the gradient, Phi g for the step and
Phi (x+ - x) for the acceptance test) and once more in each backtrack round.
What one application streams and computes is the problem's own
``operator_work``.
"""
from __future__ import annotations

from bench import spec

APPLICATIONS_PER_ITERATION = 4


def applications(counts):
    """Operator applications over (iterations run, backtrack rounds) pairs."""
    return sum(APPLICATIONS_PER_ITERATION * it + rounds for it, rounds in counts)


def least_seconds(cfg, rows_per_shard, counts, peak):
    """(seconds, bound): the least time the chip could take for the
    operator work of ``counts``; the bound is ``bytes`` or ``flops``."""
    per_bytes, per_flops = spec.problem(cfg["problem"]).operator_work(cfg, rows_per_shard)
    n = applications(counts)
    t_bytes = n * per_bytes / peak["hbm_bytes_per_s"]
    t_flops = n * per_flops / peak["matmul_flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
