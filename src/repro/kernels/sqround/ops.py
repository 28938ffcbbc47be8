"""Public wrapper: stochastic quantization of a matrix with the Pallas kernel.

``sqround(v, bits, key)`` returns (codes int8, scale) — same semantics as
``repro.quant.quantize_codes`` but (a) bit-exact reproducible from the uint32
stream, (b) executed by the TPU kernel when available.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.sqround.kernel import sqround_pallas
from repro.kernels.sqround.ref import sqround_ref


# Column tile: a 65,536-column operator row never has to fit VMEM whole.
_BLOCK_C = 512


def _round_up(v: int, mult: int) -> int:
    return (v + mult - 1) // mult * mult


def sqround(
    v: jax.Array,
    bits: int,
    key: jax.Array,
    scale: Optional[jax.Array] = None,
    *,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
    block_r: int = 256,
) -> tuple[jax.Array, jax.Array]:
    """Stochastically round a 2-D float32 array to int8 codes in [-K, K].

    Tiles are ``min(block_r, R) × min(512, C)``; a dimension larger than its
    tile is padded up to a tile multiple (``block_r`` a multiple of 32, the
    int8 output's sublane tile)."""
    if v.ndim != 2:
        raise ValueError("sqround expects a 2-D array")
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu" or interpret
    if scale is None:
        m = jnp.max(jnp.abs(v))
        scale = jnp.where(m > 0, m, 1.0).astype(jnp.float32)
    u = jax.random.bits(key, v.shape, dtype=jnp.uint32)
    if not use_pallas:
        return sqround_ref(v, u, scale, bits), scale
    r, c = v.shape
    br, bc = min(block_r, r), min(_BLOCK_C, c)
    rp, cp = _round_up(r, br), _round_up(c, bc)
    pad = ((0, rp - r), (0, cp - c))
    codes = sqround_pallas(
        jnp.pad(v, pad), jnp.pad(u, pad), scale.reshape(1, 1), bits=bits,
        block_r=br, block_c=bc, interpret=interpret,
    )
    return codes[:r, :c], scale
