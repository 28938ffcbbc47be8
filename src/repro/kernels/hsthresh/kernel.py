"""Pallas TPU kernels for the streaming hard-threshold operator H_s.

Two passes (see ref.py): a histogram kernel (block-accumulated into a single
output revisited across the grid) and an elementwise mask kernel.
Both are bandwidth-bound streaming passes over x — the same access pattern the
paper's FPGA uses for its top-S binary search, restructured so each element is
read exactly twice instead of O(log) times.

The histogram is counted on the MXU: bin ``b`` splits into a high part
``b // 128`` and a lane part ``b % 128``, and the (nbins/128, 128) count table
of a block is the product of the two one-hot matrices over the block's
elements. Both one-hots keep the elements on lanes, so no (elements, nbins)
one-hot is ever built; 0/1 operands and f32 accumulation make the counts
exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


_LANES = 128


def _hist_kernel(x_ref, vmax_ref, o_ref, *, nbins: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    mag = jnp.abs(x_ref[...])                                  # (1, bn)
    vmax = vmax_ref[0, 0]
    idx = jnp.clip((mag / vmax * nbins).astype(jnp.int32), 0, nbins - 1)
    n_hi, bn = o_ref.shape[0], idx.shape[1]
    hi = jax.lax.broadcasted_iota(jnp.int32, (n_hi, bn), 0)
    lo = jax.lax.broadcasted_iota(jnp.int32, (_LANES, bn), 0)
    onehot_hi = (idx // _LANES == hi).astype(jnp.float32)     # (n_hi, bn)
    onehot_lo = (idx % _LANES == lo).astype(jnp.float32)      # (128, bn)
    counts = jax.lax.dot_general(
        onehot_hi, onehot_lo, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                    # (n_hi, 128)
    o_ref[...] += counts.astype(jnp.int32)


def _mask_kernel(x_ref, t_ref, o_ref):
    x = x_ref[...]
    t = t_ref[0, 0]
    o_ref[...] = jnp.where(jnp.abs(x) > t, x, jnp.zeros_like(x))


@functools.partial(jax.jit, static_argnames=("nbins", "block_n", "interpret"))
def hist_pallas(
    x: jax.Array, vmax: jax.Array, *, nbins: int = 2048, block_n: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """Histogram of |x| (x: (1, N) f32, N % block_n == 0) → (1, nbins) int32.
    ``nbins`` must be a multiple of 128 (see the module docstring)."""
    n = x.shape[1]
    if n % block_n:
        raise ValueError("pad x to a multiple of block_n in ops.py")
    if nbins % _LANES:
        raise ValueError(f"nbins={nbins} must be a multiple of {_LANES}")
    n_hi = nbins // _LANES
    counts = pl.pallas_call(
        functools.partial(_hist_kernel, nbins=nbins),
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((n_hi, _LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_hi, _LANES), jnp.int32),
        interpret=interpret,
    )(x, vmax)
    return counts.reshape(1, nbins)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def mask_pallas(
    x: jax.Array, t: jax.Array, *, block_n: int = 1024, interpret: bool = False
) -> jax.Array:
    """y = where(|x| > t, x, 0) for x (1, N), N % block_n == 0."""
    n = x.shape[1]
    if n % block_n:
        raise ValueError("pad x to a multiple of block_n in ops.py")
    return pl.pallas_call(
        _mask_kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, t)
