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

One grid step, in full float32 with one MXU pass: codes of ≤ 8 bits are
integers with ``|c| ≤ 255``, which bfloat16 holds exactly, so the step unpacks
them straight to bf16. The f32 x tile is split exactly into three bf16 parts,
``x = hi + mid + lo`` (:func:`split_bf16x3`), stacked as one ``(3·bm, bk)``
LHS; one bf16 dot with f32 accumulation forms ``Σ c·hi``, ``Σ c·mid`` and
``Σ c·lo`` — every product exact in f32 — and the three ``(bm, bn)`` row blocks
are summed in f32. These are the products a ``Precision.HIGHEST`` dot forms
when one operand's low bf16 pieces are zero, without its extra MXU passes or
the per-pass VPU split of the code tile. The step does this in a loop over
chunks of at most 512 packed lanes (:func:`_chunk_lanes`), so the kernel's
code, which the TPU holds in HBM, does not grow with the step.

Grid: ``(M/bm, N/bn, K/bk)``; K is the fastest-varying (sequential on TPU), and
the output block (bm, bn) is revisited across the K steps and accumulated in
place (initialized at k==0). Every grid step costs a fixed ~0.24 µs on a TPU
v5e besides its stream, so :func:`select_block_config` sizes the tiles against a
model of one call: the grid steps' fixed cost, the codes streamed (padding
included; once per M block), x streamed (once per N block) and the first and
last steps, which the pipeline cannot overlap — under the step's VMEM estimate
(:func:`vmem_bytes`), which the kernel requests from the compiler
(:data:`VMEM_LIMIT_BYTES`). Tiles are multiples of the hardware minima, at most
the aligned problem, so a K of 870 takes one 896-code step, not a multiple of
512. :func:`qmm_pallas` reads the codes and their scale row as stored: where N
or K is not a block multiple, the grid rounds up and its last block along that
axis is partial; only x, the small operand, is padded to the K step.
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
  contraction, so it cannot multiply the accumulator directly; instead x's
  parts are expanded to one row block per scale group (masked to that group's
  columns), one dot yields every group's partial sums at once, and each
  partial is scaled on the output side. ``bk/g`` must be a multiple of 8; the
  expansion grows with ``bk/g``, so this kernel keeps the least K step.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.quant.formats import BY_BITS
from repro.quant.pack import packed_len

# Hardware minima the tiles must respect regardless of problem size: 8
# sublanes (M), 128 lanes (N), and a K step that is whole packed bytes on
# 128 lanes (128·vpb values). M blocks hold at most the MXU's 128 rows.
_MIN_BM = 8
_MIN_BN = 128
_MAX_BM = 128
# Largest N and K tiles the search considers (compile time grows with the
# tile: the kernel body is unrolled over it).
_MAX_BN = 4096
_MAX_BK = 8192
# Bytes HBM streams (819 GB/s) in the fixed cost of one grid step, about
# 0.24 µs on a TPU v5e (measured with the codes DMA'd and not computed on).
_STEP_COST_BYTES = 192 * 1024

# Scoped VMEM the kernels request from the TPU compiler (v5e has 128 MiB);
# tiles are sized against half of it so the compiler's own scratch fits too.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES // 2


def _round_up(value: int, multiple: int) -> int:
    return -(-value // multiple) * multiple


def vmem_bytes(bm: int, bn: int, bk: int, bits: int,
               group_size: int | None = None) -> int:
    """Estimated VMEM bytes of one grid step: double-buffered input and output
    blocks plus the temporaries of the codes unpacked at once (a chunk of the
    tile, :func:`_chunk_lanes`, or the whole tile for the group kernel): one
    part's int32 words, the bf16 codes, x's three parts in f32 and bf16, their
    products, and for the group kernel the group-expanded parts."""
    vpb = BY_BITS[bits].values_per_byte
    bkp = bk // vpb
    scale_blk = 4 * (max(8, bk // group_size) if group_size else 8) * bn
    blocks = 2 * (4 * bm * bk + bn * bkp + scale_blk) + 2 * 4 * bm * bn
    rows = 3 * bm * (bk // group_size if group_size else 1)
    c = bk if group_size else _chunk_lanes(bkp) * vpb
    temps = 4 * bn * c // vpb + 2 * bn * c + 6 * 3 * bm * c + 4 * rows * bn
    if group_size:
        temps += 6 * rows * bkp
    return blocks + temps


def _call_cost(m: int, n: int, k_dim: int, bm: int, bn: int, bk: int,
               vpb: int) -> int:
    """Modelled cost of one call in streamed bytes: the fixed cost of every
    grid step, the padded codes (once per M block), x (once per N block), and
    the first and last steps' blocks, which the pipeline cannot overlap."""
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k_dim, bk)
    steps = (mp // bm) * (np_ // bn) * (kp // bk)
    streamed = (mp // bm) * np_ * kp // vpb + (np_ // bn) * mp * kp * 4
    return steps * _STEP_COST_BYTES + streamed + 2 * (bn * bk // vpb + 4 * bm * bk)


def _candidates(cap: int, unit: int, limit: int) -> list[int]:
    """Tiles to try along one axis: the unit's power-of-two multiples and the
    aligned problem size itself, none above ``limit`` (nor ``cap``)."""
    out = {cap} if cap <= limit else set()
    t = unit
    while t <= min(cap, limit):
        out.add(t)
        t *= 2
    return sorted(out)


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

    Auto mode (a block dim left ``None``): ``bm`` is the problem's rows
    rounded up to 8, at most 128. ``bn`` and ``bk`` are searched over the
    hardware minimum's power-of-two multiples and the aligned problem size
    (128 lanes for N; ``128·vpb`` codes for K, lcm'd with ``8·group_size`` for
    the group kernel, whose K step stays at that unit): the pair whose
    modelled call cost (:func:`_call_cost`) is least, among those whose
    :func:`vmem_bytes` fits :data:`VMEM_BUDGET_BYTES`, ties to the smaller tile.

    Explicit mode (a block dim passed): validate strictly — misaligned blocks,
    a K step that is not a whole number of 8-row scale slabs (group kernel),
    a block that exceeds the aligned problem size (pure padding), or a tile
    set over the VMEM budget raise ``ValueError`` instead of silently blowing
    up the padded footprint or failing in the TPU compiler.
    """
    vpb = BY_BITS[bits].values_per_byte
    k_unit = 128 * vpb
    if group_size is not None:
        # the transposed scale slab (bk/g, bn) must tile 8 sublanes
        k_unit = math.lcm(k_unit, 8 * group_size)

    m_cap = _round_up(max(m, 1), _MIN_BM)
    n_cap = _round_up(max(n, 1), _MIN_BN)
    k_cap = _round_up(max(k_dim, 1), k_unit)

    if block_m is None:
        bm = min(_MAX_BM, m_cap)
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
        bns = _candidates(n_cap, _MIN_BN, _MAX_BN)
    else:
        if block_n % _MIN_BN:
            raise ValueError(f"block_n={block_n} must be a multiple of {_MIN_BN}")
        if block_n > n_cap:
            raise ValueError(
                f"block_n={block_n} exceeds aligned problem size {n_cap} (n={n}): "
                "the tile would be mostly padding; shrink it or leave it unset"
            )
        bns = [block_n]
    if block_k is None:
        bks = [k_unit] if group_size else _candidates(k_cap, k_unit, _MAX_BK)
    else:
        if block_k % k_unit:
            raise ValueError(
                f"block_k={block_k} must be a multiple of {k_unit} "
                f"(128 lanes × values/byte at {bits} bits"
                + (f", lcm 8 × group_size={group_size}" if group_size else "")
                + ")"
            )
        if block_k > k_cap:
            raise ValueError(
                f"block_k={block_k} exceeds aligned problem size {k_cap} (k={k_dim}): "
                "the tile would be mostly padding; shrink it or leave it unset"
            )
        bks = [block_k]

    while True:
        fits = [(_call_cost(m_cap, n, k_dim, bm, bn, bk, vpb), bn * bk, bn, bk)
                for bn in bns for bk in bks
                if vmem_bytes(bm, bn, bk, bits, group_size) <= VMEM_BUDGET_BYTES]
        if fits:
            _, _, bn, bk = min(fits)
            return bm, bn, bk
        if block_m is None and bm > _MIN_BM:
            bm = _round_up(bm // 2, _MIN_BM)
            continue
        bn, bk = bns[0], bks[0]
        raise ValueError(
            f"blocks ({bm}, {bn}, {bk}) at {bits} bits need "
            f"~{vmem_bytes(bm, bn, bk, bits, group_size)} B of VMEM, over "
            f"the {VMEM_BUDGET_BYTES} B budget; shrink them or leave them "
            "unset")


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


def _bf16(v: jnp.ndarray) -> jnp.ndarray:
    """Cast an MXU operand to bf16: codes and x's split parts are exact in it,
    and where the split rounds x, its later parts carry what was dropped."""
    # jaxlint: allow=JL001 -- see the docstring: the parts lose nothing of x
    return v.astype(jnp.bfloat16)


def split_bf16x3(x: jnp.ndarray) -> jnp.ndarray:
    """f32 (r, c) → (3r, c) f32 rows ``[hi; mid; lo]``, each exactly a bf16,
    with ``hi + mid + lo == x`` exactly: each part takes the next 8 significant
    bits of what the parts before it left, and f32 holds 24. Exact for 0 and
    for every ``2^-100 ≤ |x| < 2^127`` (no part underflows or overflows)."""
    hi = _bf16(x).astype(jnp.float32)
    rest = x - hi
    mid = _bf16(rest).astype(jnp.float32)
    return jnp.concatenate([hi, mid, rest - mid], axis=0)


def _unpack_parts(w_packed_blk: jnp.ndarray, bits: int) -> list[jnp.ndarray]:
    """(bn, bkp) uint8 → vpb lane-dense (bn, bkp) bf16 unit-scale code blocks
    (exact: every code is an integer of at most 8 bits); part ``i`` holds the
    codes ``j·vpb + i`` of the tile."""
    fmt = BY_BITS[bits]
    k = fmt.half_steps
    w32 = w_packed_blk.astype(jnp.int32)
    if fmt.values_per_byte == 1:
        fields = [w32]
    else:
        mask = (1 << bits) - 1
        fields = [(w32 >> (bits * i)) & mask for i in range(fmt.values_per_byte)]
    return [_bf16((f - k).astype(jnp.float32)) for f in fields]


def _dot_nt(a, b):
    """(r, c) × (n, c) → (r, n), contracting the lane axis of both: one MXU
    pass over bf16 operands, products and accumulation in f32."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _chunk_lanes(bkp: int) -> int:
    """Packed lanes of the code tile that one iteration of :func:`_qmm_kernel`
    unpacks and multiplies: the largest multiple of 128 lanes, at most 512,
    that divides the tile's ``bkp``. The loop keeps the kernel's code (which
    the TPU holds in HBM beside the operator) and its temporaries the size of
    one chunk, whatever the grid step streams."""
    return max(c for c in range(128, min(bkp, 512) + 1, 128) if bkp % c == 0)


def _qmm_kernel(x_ref, w_ref, scale_ref, o_ref, *, bits: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bm = x_ref.shape[0]
    bkp = w_ref.shape[1]
    cp = _chunk_lanes(bkp)
    scale = scale_ref[...] / BY_BITS[bits].half_steps     # (1, bn)

    def chunk(j, carry):
        off = pl.multiple_of(j * cp, cp)
        parts = _unpack_parts(w_ref[:, pl.ds(off, cp)], bits)   # vpb × (bn, cp)
        acc = None
        for i, part in enumerate(parts):
            x_i = x_ref[:, pl.ds(pl.multiple_of(i * bkp + off, cp), cp)]
            xs = _bf16(split_bf16x3(x_i.astype(jnp.float32)))   # (3·bm, cp)
            d = _dot_nt(xs, part)                              # (3·bm, bn)
            acc = d if acc is None else acc + d
        o_ref[...] += (acc[:bm] + acc[bm:2 * bm] + acc[2 * bm:]) * scale
        return carry

    jax.lax.fori_loop(0, bkp // cp, chunk, 0)


def _qmm_group_kernel(x_ref, w_ref, scale_ref, o_ref, *, bits: int, group_size: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    parts = _unpack_parts(w_ref[...], bits)             # vpb × (bn, bkp)
    bkp = parts[0].shape[1]
    scales = scale_ref[...]                             # (bkg, bn), transposed
    bkg, bn = scales.shape
    bm = x_ref.shape[0]
    xs = split_bf16x3(x_ref[...].astype(jnp.float32))   # (3·bm, bk) f32
    # within every part, column j belongs to scale group j // (g / vpb)
    gp = group_size // BY_BITS[bits].values_per_byte
    grp = jax.lax.broadcasted_iota(jnp.int32, (bkg, 1, bkp), 0) * gp
    col = jax.lax.broadcasted_iota(jnp.int32, (bkg, 1, bkp), 2)
    in_group = (col >= grp) & (col < grp + gp)
    acc = None
    for i, part in enumerate(parts):
        x_i = xs[:, i * bkp:(i + 1) * bkp]              # (3·bm, bkp)
        # row block q of x_g is x's parts restricted to scale group q's columns
        x_g = jnp.where(in_group, x_i[None], 0.0).reshape(bkg * 3 * bm, bkp)
        d = _dot_nt(_bf16(x_g), part)                   # (bkg·3·bm, bn) partials
        acc = d if acc is None else acc + d
    acc = acc.reshape(bkg, 3, bm, bn)
    scaled = (acc[:, 0] + acc[:, 1] + acc[:, 2]) * scales[:, None, :]
    o_ref[...] += jnp.sum(scaled, axis=0) * (1.0 / BY_BITS[bits].half_steps)


_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


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
        compiler_params=_COMPILER_PARAMS,
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
    """Packed low-precision matmul over the codes as they are stored.

    x (M, Kp) with M a multiple of ``block_m``, Kp = ``k_dim`` rounded up to
    ``block_k``, and every column of x past ``k_dim`` zero; w_packed
    (N, packed_len(k_dim)) uint8 and scale (1, N) f32 for any N. Returns
    (M, N) f32.

    Nothing of the codes or the scale row is copied: where N or the packed K
    is not a block multiple, the grid's last block along that axis is partial.
    Out-of-bounds lanes of a partial output block are never written, so the
    code rows and scale lanes past N reach nothing. Codes past K are whatever
    the last block's DMA leaves in VMEM — uint8 words, so finite once
    unpacked — and meet x's zero columns, so each adds an exact zero."""
    fmt = BY_BITS[bits]
    vpb = fmt.values_per_byte
    m, k = x.shape
    n = w_packed.shape[0]
    if k != _round_up(k_dim, block_k):
        raise ValueError(f"x K dim {k} != k_dim {k_dim} rounded up to block_k "
                         f"{block_k}; pad x in ops.py")
    if m % block_m:
        raise ValueError(f"x rows {m} must be a multiple of block_m {block_m}; "
                         "pad x in ops.py")
    if w_packed.shape[1] != packed_len(k_dim, bits):
        raise ValueError("w_packed minor dim inconsistent with k_dim/bits")
    if scale.shape != (1, n):
        raise ValueError(f"scale shape {scale.shape} != (1, N) = (1, {n})")
    bk_packed = block_k // vpb
    grid = (m // block_m, pl.cdiv(n, block_n), k // block_k)
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
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(planar_x(x, bits, block_k), w_packed, scale)
