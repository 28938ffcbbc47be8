"""JL004 fixture (clean): the PR 5 fix shape — while_loop over iterations
(element-uniform trip decision) and masked arithmetic instead of branches."""
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map


@partial(shard_map, mesh=None, in_specs=None, out_specs=None)
def solve_shard(y):
    def body(st):
        k, v = st
        return k + 1, jnp.where(v.sum() > 0, v * 2.0, v)

    def cond_fn(st):
        return st[0] < 4

    return lax.while_loop(cond_fn, body, (0, y))[1]


def batched(xs):
    def per_row(x):
        return jnp.where(x[0] > 0, x * 2.0, x)

    return jax.vmap(per_row)(xs)


def unmapped(y):
    # cond OUTSIDE any SPMD wrapper is fine — both-branch execution only
    # bites under shard_map/vmap tracing
    return lax.cond(y.sum() > 0, lambda v: v * 2.0, lambda v: v, y)
