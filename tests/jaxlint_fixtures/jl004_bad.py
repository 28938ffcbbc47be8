"""JL004 fixture: the PR 5 trap — lax.cond under shard_map/vmap is rewritten
to select, so BOTH branches run on every element."""
from functools import partial

import jax
from jax import lax
from jax import shard_map


@partial(shard_map, mesh=None, in_specs=None, out_specs=None)
def solve_shard(y):
    # BUG: under SPMD both branches execute — the "skip the solve" branch
    # does not skip anything
    return lax.cond(y.sum() > 0, lambda v: v * 2.0, lambda v: v, y)


def batched(xs):
    def per_row(x):
        # BUG: vmap batches cond into select — both branches per row
        return lax.cond(x[0] > 0, expensive, cheap, x)

    return jax.vmap(per_row)(xs)


def expensive(x):
    return x * 2.0


def cheap(x):
    return x
