"""Pallas TPU kernel: packed low-precision matmul (the paper's hot spot).

TPU adaptation of the paper's AVX2/FPGA low-precision dot-product engines: the
*packed* integer codes are what stream HBM→VMEM (4–16× fewer bytes than f32),
unpacking is shift/mask arithmetic on VMEM-resident vregs, and the MXU does the
f32-accumulated matmul on 128-aligned tiles. Performance is therefore bound by
``size(Φ̂)/BW_HBM`` — the same precision-proportional law as the paper's
``T = size(Φ)/P`` on FPGA (supplementary §8.1).

Layout contract (see ref.py): ``y[m, n] = Σ_k x[m, k] · ŵ[n, k]`` with ``ŵ``
packed along K (minor-most axis → contiguous packed words), byte ``j`` of a row
holding codes ``j·vpb + i`` in its bit field ``i`` (``vpb`` = values per byte).

Planar pairing: a K-tile of ``bk`` codes arrives as ``bk/vpb`` packed bytes per
row. Shifting out bit field ``i`` gives a *part* — a lane-dense ``(bn, bk/vpb)``
block of the codes ``j·vpb + i``. Interleaving the parts back into storage order
is a lane relayout the TPU compiler cannot fit in VMEM at 2 and 4 bits, so the
kernel never does it: the wrappers permute ``x``'s columns once per call
(:func:`planar_x`) so that part ``i`` pairs with the contiguous lane slice
``[i·bk/vpb, (i+1)·bk/vpb)`` of the x tile, and the tile's dot is the sum of
``vpb`` part dots. The packed format itself is unchanged (one format for the CPU
fused path, the oracle and this kernel).

Grid: ``(M/bm, N/bn, K/bk)``; K is the fastest-varying (sequential on TPU), and
the output block (bm, bn) is revisited across the K steps and accumulated in
place (initialized at k==0). Block shapes are chosen by
:func:`select_block_config`: MXU-aligned ``bm=128, bn=128, bk=512`` for large
problems, clamped down to the aligned problem size for small ones so tiny
shapes (the recovery benchmarks run m=64, n=128) are not dwarfed by padding,
and shrunk until the step's VMEM estimate (:func:`vmem_bytes`) fits the budget.
Explicitly passed block shapes are validated strictly — misalignment, a block
that pads the problem more than the hardware minima require, or one over the
VMEM budget raises instead of failing in the TPU compiler.

Two scale layouts, two kernels:

* :func:`qmm_pallas`       — one scale per output channel, shape (1, N): the
  dot runs on unit-scale codes and the scale multiplies the *accumulated*
  (bm, bn) block (cheapest; per_tensor is the broadcast special case).
* :func:`qmm_group_pallas` — blockwise scales along the contraction axis,
  shape (N, K/g), streamed transposed as (K/g, N) so each K-tile's scale slab
  ``(bk/g, bn)`` is a legal (8, 128)-tiled block. The scale varies within the
  contraction, so it cannot multiply the accumulator directly; instead the x
  tile is expanded to one row block per scale group (x masked to that group's
  columns), one dot yields every group's partial sums at once, and each
  partial is scaled on the output side. ``bk/g`` must be a multiple of 8.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.quant.formats import BY_BITS

# Hardware minima the tiles must respect regardless of problem size: 8
# sublanes (M), 128 lanes (N), and a K step that is whole packed bytes on
# 128 lanes (128·vpb values). Defaults below are the MXU sweet spot for
# large problems; select_block_config clamps them to the aligned problem.
_MIN_BM = 8
_MIN_BN = 128
_DEFAULT_BM = 128
_DEFAULT_BN = 128
_DEFAULT_BK = 512

# The TPU compiler grants a kernel 16 MiB of scoped VMEM by default (v5e);
# tiles are sized against half of it so the compiler's own scratch fits too.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024


def _round_up(value: int, multiple: int) -> int:
    return -(-value // multiple) * multiple


def vmem_bytes(bm: int, bn: int, bk: int, bits: int,
               group_size: int | None = None) -> int:
    """Estimated VMEM bytes of one grid step: double-buffered input and output
    blocks plus the in-kernel temporaries (unpacked parts, and for the group
    kernel the group-expanded x tile and its partial sums)."""
    vpb = BY_BITS[bits].values_per_byte
    bkp = bk // vpb
    scale_blk = 4 * (max(8, bk // group_size) if group_size else 8) * bn
    blocks = 2 * (4 * bm * bk + bn * bkp + scale_blk) + 2 * 4 * bm * bn
    temps = 4 * bn * bkp + 2 * 4 * bn * bk      # int32 words + f32 parts
    if group_size:
        rows = (bk // group_size) * bm
        temps += 4 * rows * bk + 2 * 4 * rows * bn
    return blocks + temps


def select_block_config(
    m: int,
    n: int,
    k_dim: int,
    bits: int,
    *,
    group_size: int | None = None,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
) -> tuple[int, int, int]:
    """Choose (bm, bn, bk) for a packed matmul of logical shape (m, k)×(n, k).

    Auto mode (a block dim left ``None``): start from the MXU defaults and
    clamp each tile to the problem dimension rounded up to its hardware
    minimum, so small problems (the fig5 bench runs m=64, n=128) pay only the
    unavoidable alignment padding instead of a full 128×128×512 tile; then
    shrink the free dims (bk first, then bm, then bn) until
    :func:`vmem_bytes` fits :data:`VMEM_BUDGET_BYTES`.

    Explicit mode (a block dim passed): validate strictly — misaligned blocks,
    a K step that is not a whole number of 8-row scale slabs (group kernel),
    a block that exceeds the aligned problem size (pure padding), or a tile
    set over the VMEM budget raise ``ValueError`` instead of silently blowing
    up the padded footprint or failing in the TPU compiler.
    """
    k_unit = 128 * BY_BITS[bits].values_per_byte
    if group_size is not None:
        # the transposed scale slab (bk/g, bn) must tile 8 sublanes
        k_unit = math.lcm(k_unit, 8 * group_size)

    m_cap = _round_up(max(m, 1), _MIN_BM)
    n_cap = _round_up(max(n, 1), _MIN_BN)
    k_cap = _round_up(max(k_dim, 1), k_unit)

    if block_m is None:
        bm = min(_DEFAULT_BM, m_cap)
    else:
        bm = block_m
        if bm % _MIN_BM:
            raise ValueError(f"block_m={bm} must be a multiple of {_MIN_BM}")
        if bm > m_cap:
            raise ValueError(
                f"block_m={bm} exceeds aligned problem size {m_cap} (m={m}): "
                "the tile would be mostly padding; shrink it or leave it unset"
            )
    if block_n is None:
        bn = min(_DEFAULT_BN, n_cap)
    else:
        bn = block_n
        if bn % _MIN_BN:
            raise ValueError(f"block_n={bn} must be a multiple of {_MIN_BN}")
        if bn > n_cap:
            raise ValueError(
                f"block_n={bn} exceeds aligned problem size {n_cap} (n={n}): "
                "the tile would be mostly padding; shrink it or leave it unset"
            )
    if block_k is None:
        bk = min(_round_up(_DEFAULT_BK, k_unit), k_cap)
    else:
        bk = block_k
        if bk % k_unit:
            raise ValueError(
                f"block_k={bk} must be a multiple of {k_unit} "
                f"(128 lanes × values/byte at {bits} bits"
                + (f", lcm 8 × group_size={group_size}" if group_size else "")
                + ")"
            )
        if bk > k_cap:
            raise ValueError(
                f"block_k={bk} exceeds aligned problem size {k_cap} (k={k_dim}): "
                "the tile would be mostly padding; shrink it or leave it unset"
            )

    while vmem_bytes(bm, bn, bk, bits, group_size) > VMEM_BUDGET_BYTES:
        if block_k is None and bk > k_unit:
            bk = _round_up(bk // 2, k_unit)
        elif block_m is None and bm > _MIN_BM:
            bm = _round_up(bm // 2, _MIN_BM)
        elif block_n is None and bn > _MIN_BN:
            bn = _round_up(bn // 2, _MIN_BN)
        else:
            raise ValueError(
                f"blocks ({bm}, {bn}, {bk}) at {bits} bits need "
                f"~{vmem_bytes(bm, bn, bk, bits, group_size)} B of VMEM, over "
                f"the {VMEM_BUDGET_BYTES} B budget; shrink them or leave them "
                "unset")
    return bm, bn, bk


def planar_x(x: jax.Array, bits: int, block_k: int) -> jax.Array:
    """Permute the columns of x (M, K) within every K-tile of ``block_k`` so
    that column ``j·vpb + i`` of the tile moves to ``i·(block_k/vpb) + j``:
    part ``i`` of the tile's packed codes then pairs with a contiguous lane
    slice of x (see the module docstring). Identity at 8 bits."""
    vpb = BY_BITS[bits].values_per_byte
    if vpb == 1:
        return x
    m, k = x.shape
    return (x.reshape(m, k // block_k, block_k // vpb, vpb)
            .swapaxes(2, 3).reshape(m, k))


def _unpack_parts(w_packed_blk: jnp.ndarray, bits: int) -> list[jnp.ndarray]:
    """(bn, bkp) uint8 → vpb lane-dense (bn, bkp) f32 unit-scale code blocks;
    part ``i`` holds the codes ``j·vpb + i`` of the tile."""
    fmt = BY_BITS[bits]
    k = fmt.half_steps
    w32 = w_packed_blk.astype(jnp.int32)
    if fmt.values_per_byte == 1:
        return [(w32 - k).astype(jnp.float32)]
    mask = (1 << bits) - 1
    return [(((w32 >> (bits * i)) & mask) - k).astype(jnp.float32)
            for i in range(fmt.values_per_byte)]


def _dot_nt(a, b):
    """(r, c) × (n, c) → (r, n), contracting the lane axis of both, in full
    f32 (the MXU default would round the f32 x operand to bf16)."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _qmm_kernel(x_ref, w_ref, scale_ref, o_ref, *, bits: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    parts = _unpack_parts(w_ref[...], bits)             # vpb × (bn, bkp)
    bkp = parts[0].shape[1]
    acc = None
    for i, part in enumerate(parts):
        x_i = x_ref[:, i * bkp:(i + 1) * bkp].astype(jnp.float32)   # (bm, bkp)
        d = _dot_nt(x_i, part)
        acc = d if acc is None else acc + d
    o_ref[...] += acc * (scale_ref[...] / BY_BITS[bits].half_steps)  # (1, bn) bcast


def _qmm_group_kernel(x_ref, w_ref, scale_ref, o_ref, *, bits: int, group_size: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    parts = _unpack_parts(w_ref[...], bits)             # vpb × (bn, bkp)
    bkp = parts[0].shape[1]
    scales = scale_ref[...]                             # (bkg, bn), transposed
    bkg, bn = scales.shape
    bm = x_ref.shape[0]
    # within every part, column j belongs to scale group j // (g / vpb)
    gp = group_size // BY_BITS[bits].values_per_byte
    grp = jax.lax.broadcasted_iota(jnp.int32, (bkg, 1, bkp), 0) * gp
    col = jax.lax.broadcasted_iota(jnp.int32, (bkg, 1, bkp), 2)
    in_group = (col >= grp) & (col < grp + gp)
    acc = None
    for i, part in enumerate(parts):
        x_i = x_ref[:, i * bkp:(i + 1) * bkp].astype(jnp.float32)   # (bm, bkp)
        # row block q of x_g is x restricted to scale group q's columns
        x_g = jnp.where(in_group, x_i[None], 0.0).reshape(bkg * bm, bkp)
        d = _dot_nt(x_g, part)                          # (bkg·bm, bn) partials
        acc = d if acc is None else acc + d
    scaled = acc.reshape(bkg, bm, bn) * scales[:, None, :]
    o_ref[...] += jnp.sum(scaled, axis=0) * (1.0 / BY_BITS[bits].half_steps)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "k_dim", "group_size", "block_m", "block_n", "block_k",
                     "interpret"),
)
def qmm_group_pallas(
    x: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array,
    *,
    bits: int,
    k_dim: int,
    group_size: int,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Group-scaled packed matmul. Shapes must be pre-padded to block multiples:
    x (M, K), w_packed (N, K/vpb) uint8, scale (N, K/g) f32. Returns (M, N) f32."""
    fmt = BY_BITS[bits]
    vpb = fmt.values_per_byte
    m, k = x.shape
    n = w_packed.shape[0]
    if k != k_dim:
        raise ValueError(f"x K dim {k} != k_dim {k_dim}")
    if k % block_k or m % block_m or n % block_n:
        raise ValueError(f"shapes ({m},{k}),({n}) must be multiples of blocks "
                         f"({block_m},{block_n},{block_k}); pad in ops.py")
    if block_k % (8 * group_size):
        raise ValueError(f"block_k {block_k} must be a multiple of 8 × group_size "
                         f"{group_size} (the scale slab tiles 8 sublanes)")
    if w_packed.shape[1] * vpb != k:
        raise ValueError("w_packed minor dim inconsistent with k_dim/bits")
    if scale.shape != (n, k // group_size):
        raise ValueError(f"scale shape {scale.shape} != (N, K/g) = ({n}, {k // group_size})")
    bk_packed = block_k // vpb
    bk_groups = block_k // group_size
    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        functools.partial(_qmm_group_kernel, bits=bits, group_size=group_size),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_n, bk_packed), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((bk_groups, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(planar_x(x, bits, block_k), w_packed, scale.T)


@functools.partial(
    jax.jit, static_argnames=("bits", "k_dim", "block_m", "block_n", "block_k", "interpret")
)
def qmm_pallas(
    x: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array,
    *,
    bits: int,
    k_dim: int,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Packed low-precision matmul. Shapes must be pre-padded to block multiples:
    x (M, K), w_packed (N, K/vpb) uint8, scale (1, N). Returns (M, N) f32."""
    fmt = BY_BITS[bits]
    vpb = fmt.values_per_byte
    m, k = x.shape
    n = w_packed.shape[0]
    if k != k_dim:
        raise ValueError(f"x K dim {k} != k_dim {k_dim}")
    if k % block_k or m % block_m or n % block_n:
        raise ValueError(f"shapes ({m},{k}),({n}) must be multiples of blocks "
                         f"({block_m},{block_n},{block_k}); pad in ops.py")
    if w_packed.shape[1] * vpb != k:
        raise ValueError("w_packed minor dim inconsistent with k_dim/bits")
    bk_packed = block_k // vpb
    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        functools.partial(_qmm_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_n, bk_packed), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(planar_x(x, bits, block_k), w_packed, scale)
