"""Distributed-optimization collectives: quantized gradient all-reduce — the
MODEL-TRAINING half of the distribution layer.

The paper's stochastic quantizer applied to the *communication* side of
training (the authors' QSGD/ZipML lineage): gradients are compressed to b-bit
integer codes before the cross-replica sum. Two-phase protocol keeps the sum
exact over the integer grid:

    1. global scale  s  = pmax(max|g|)          (tiny collective)
    2. codes         c  = stochastic_round(g / s · K)   (int32)
    3. sum           C  = psum(c)               (the big collective, b-bit payload)
    4. result        ĝ  = C · s / (K · n)       (unbiased mean)

Intended placement: *inter-pod* gradient sync — intra-pod ICI runs
full-precision SPMD; the slower pod-to-pod links carry compressed codes.
Implemented with ``shard_map``; optional error-feedback residual accumulation
turns the per-step quantization error into a correction at the next step.

The SOLVER mesh story is intentionally different: the sharded recovery path
(:mod:`repro.parallel.batch`) contains NO collectives at all — independent
observations of one Φ̂ are row-sharded over a 1-D ``("batch",)`` mesh and
never communicate, which is why its results are per-item identical to the
single-device run rather than merely unbiased. These gradient collectives
apply only to the LM-twin training workloads (``docs/architecture.md`` maps
both halves).

(The HLO emitted on CPU carries int32 psum — the byte saving is realized by
the int8/int4 all-reduce path on real interconnects; the *numerics* here are
exactly what production would see, which is what the tests verify.)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.quant.formats import BY_BITS


def _quantize_shard(g: jax.Array, scale: jax.Array, bits: int, key: jax.Array):
    k = BY_BITS[bits].half_steps
    scaled = jnp.clip(g / scale, -1.0, 1.0) * k
    low = jnp.floor(scaled)
    p_up = scaled - low
    u = jax.random.uniform(key, g.shape, jnp.float32)
    return jnp.clip(low + (u < p_up), -k, k).astype(jnp.int32)


def quantized_allreduce_mean(
    g: jax.Array,
    *,
    axis_name: str,
    bits: int,
    key: jax.Array,
    residual: Optional[jax.Array] = None,
):
    """Inside shard_map/pmap: unbiased quantized mean over ``axis_name``.

    Returns (mean_grad, new_residual). With ``residual`` given, applies error
    feedback: the local quantization error is added back next round.
    """
    k = BY_BITS[bits].half_steps
    n = jax.lax.psum(1, axis_name)
    g_in = g + (residual if residual is not None else 0.0)
    scale = jax.lax.pmax(jnp.max(jnp.abs(g_in)), axis_name)
    scale = jnp.maximum(scale, 1e-30)
    codes = _quantize_shard(g_in, scale, bits, key)
    sent = codes.astype(jnp.float32) * (scale / k)       # what the wire carries
    new_residual = g_in - sent if residual is not None else None
    total = jax.lax.psum(codes, axis_name)
    mean = total.astype(jnp.float32) * (scale / k) / n
    return mean, new_residual


def make_qgrad_allreduce(mesh: Mesh, axis_name: str, bits: int):
    """A pytree-level quantized-mean all-reduce over one mesh axis, as a
    shard_map'd function: tree, key -> tree (mean over axis replicas)."""

    def per_shard(flat_g, key):
        outs = []
        for i, g in enumerate(flat_g):
            m, _ = quantized_allreduce_mean(
                g, axis_name=axis_name, bits=bits, key=jax.random.fold_in(key, i)
            )
            outs.append(m)
        return tuple(outs)

    def run(tree, key):
        flat, treedef = jax.tree_util.tree_flatten(tree)
        specs = tuple(P(axis_name, *([None] * (g.ndim - 1))) for g in flat)
        fn = jax.shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(specs, P()),
            out_specs=tuple(P(None, *([None] * (g.ndim - 1))) for g in flat),
        )
        out = fn(tuple(flat), key)
        return jax.tree_util.tree_unflatten(treedef, list(out))

    return run


def fake_grad_compression(grads, bits: int, key: jax.Array):
    """Numerical twin of the quantized all-reduce for pjit-managed steps:
    applies the same unbiased quantize-dequantize to each gradient leaf
    (per-tensor global scale). Used when XLA owns the collective schedule."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    k = BY_BITS[bits].half_steps
    outs = []
    for i, g in enumerate(leaves):
        gf = g.astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(gf)), 1e-30)
        kk = jax.random.fold_in(key, i)
        scaled = jnp.clip(gf / scale, -1, 1) * k
        low = jnp.floor(scaled)
        u = jax.random.uniform(kk, g.shape, jnp.float32)
        codes = jnp.clip(low + (u < (scaled - low)), -k, k)
        outs.append((codes * scale / k).astype(g.dtype))
    return jax.tree_util.tree_unflatten(treedef, outs)
