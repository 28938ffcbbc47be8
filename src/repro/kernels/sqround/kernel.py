"""Pallas TPU kernel: stochastic rounding to the odd-level integer grid.

The paper's CPU implementation burns XORShift + AVX2 lanes on this (§9); on TPU
it is a pure VPU elementwise kernel. Random words are generated *outside* with
``jax.random.bits`` (counter-based, reproducible) and streamed as an operand —
this keeps the kernel deterministic given its inputs and bit-exact against the
``ref.py`` oracle (validated in interpret mode).

Grid: 2-D over (block_r, block_c) tiles of the (rows, cols) array, so a wide
matrix (a 65,536-column sensing operator) never has to fit VMEM a whole row
block at a time. The TPU has no unsigned-to-float convert: the 24 random bits
are reinterpreted as int32 (exact, they are below 2^24) before the cast.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.quant.formats import BY_BITS


def _sqround_kernel(v_ref, u_ref, scale_ref, o_ref, *, bits: int):
    k = BY_BITS[bits].half_steps
    v = v_ref[...]
    scale = scale_ref[0, 0]
    scaled = jnp.clip(v / scale, -1.0, 1.0) * k
    low = jnp.floor(scaled)
    p_up = scaled - low
    u24 = jax.lax.bitcast_convert_type(u_ref[...] >> jnp.uint32(8), jnp.int32)
    u01 = u24.astype(jnp.float32) * jnp.float32(2.0**-24)
    codes = low + (u01 < p_up).astype(jnp.float32)
    o_ref[...] = jnp.clip(codes, -k, k).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("bits", "block_r", "block_c", "interpret"))
def sqround_pallas(
    v: jax.Array,
    u: jax.Array,
    scale: jax.Array,
    *,
    bits: int,
    block_r: int = 256,
    block_c: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Stochastically round (R, C) float32 ``v`` to int8 codes.
    R % block_r == 0 and C % block_c == 0 (pad in ops.py)."""
    r, c = v.shape
    if r % block_r or c % block_c:
        raise ValueError(f"shape {(r, c)} not a multiple of blocks "
                         f"({block_r}, {block_c}); pad in ops.py")
    tile = pl.BlockSpec((block_r, block_c), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_sqround_kernel, bits=bits),
        grid=(r // block_r, c // block_c),
        in_specs=[tile, tile, pl.BlockSpec((1, 1), lambda i, j: (0, 0))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.int8),
        interpret=interpret,
    )(v, u, scale)
