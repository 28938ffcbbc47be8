"""Jit'd public wrappers around the packed low-precision matmul.

* :func:`pack_weights` — quantize + pack a weight/measurement matrix for qmm.
* :func:`qmm` — dispatch: Pallas kernel on TPU, fused blocked
  pipeline (:func:`qmm_fused`) elsewhere.
* :func:`qmm_complex` — complex Φ̂ × real/complex vectors via real matmuls.
* :class:`PackedOperator` / :func:`pack_operator` — both orientations of a CS
  measurement matrix (Φ̂ and Φ̂†), the pair QNIHT streams every iteration;
  ``shared=True`` packs one quantization in both orientations (the
  ``requantize="fixed"`` deployment mode behind ``qniht(backend="packed")``).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from repro.kernels.qmm.kernel import qmm_group_pallas, qmm_pallas, select_block_config
from repro.quant.formats import (
    BY_BITS,
    PER_CHANNEL,
    PER_TENSOR,
    Granularity,
    as_granularity,
)
from repro.quant.pack import pack_codes, validate_group_packing
from repro.quant.quantize import expand_block_scale, quantize, quantize_codes


def _round_up(v: int, mult: int) -> int:
    return (v + mult - 1) // mult * mult


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PackedWeights:
    """(N, K) weight matrix quantized & packed along K.

    ``scale`` layout follows ``granularity``: (1, N) per-output-channel f32 for
    ``per_tensor``/``per_channel`` (per-tensor broadcasts one value), or
    (N, ⌈K/group_size⌉) blockwise-along-K for ``per_block`` (consumed by the
    group-scaled kernel, which dequantizes inside the contraction).

    Registered pytree: the arrays (``packed``, ``scale``) are children and the
    config (``bits``, ``k_dim``, ``granularity``) is aux data, so packed
    weights — and every operator built from them — cross jit/shard_map
    boundaries as ordinary arguments (e.g. a pre-packed Φ̂ handed to the
    sharded serving loop, :class:`repro.parallel.batch.BatchServer`).
    """

    packed: jax.Array      # (N, packed_len(K)) uint8
    scale: jax.Array       # see granularity note above
    bits: int
    k_dim: int
    granularity: Granularity = PER_TENSOR

    def tree_flatten(self):
        return (self.packed, self.scale), (self.bits, self.k_dim, self.granularity)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def nbytes(self) -> int:
        """Packed code bytes only (the precision-proportional stream the paper's
        bandwidth law counts); the f32 scale overhead is ``scale_nbytes``."""
        return self.packed.size  # uint8

    @property
    def scale_nbytes(self) -> int:
        """Bytes of actual scale information at this granularity (per_tensor is
        ONE f32 even though the stored array broadcasts it to (1, N))."""
        return self.granularity.scale_nbytes((self.packed.shape[0], self.k_dim))


def _resolve_granularity(granularity, per_channel: bool) -> Granularity:
    """Map the legacy ``per_channel`` bool and the new ``granularity`` arg onto
    one :class:`Granularity` (an explicit granularity wins)."""
    if granularity is not None:
        return as_granularity(granularity)
    return PER_CHANNEL if per_channel else PER_TENSOR


def pack_weights(
    w: jax.Array,
    bits: int,
    key: Optional[jax.Array] = None,
    per_channel: bool = True,
    granularity: Union[Granularity, str, None] = None,
) -> PackedWeights:
    """Quantize (stochastic if key given) and pack an (N, K) real matrix.

    ``granularity`` (overrides the legacy ``per_channel`` bool when given):
    ``per_tensor`` — one scale; ``per_channel`` — one scale per output row N;
    ``per_block(g)`` — one scale per g contiguous K elements (g a multiple of
    the packing word, see :func:`repro.quant.pack.validate_group_packing`).
    """
    if w.ndim != 2:
        raise ValueError("pack_weights expects (N, K)")
    gran = _resolve_granularity(granularity, per_channel)
    if gran.kind == "per_block":
        validate_group_packing(gran.group_size, bits)
        codes, scale = quantize_codes(w, bits, key, granularity=gran)
        return PackedWeights(
            packed=pack_codes(codes, bits),
            scale=scale.astype(jnp.float32),            # (N, ⌈K/g⌉)
            bits=bits,
            k_dim=w.shape[1],
            granularity=gran,
        )
    if gran.kind == "per_channel":
        codes, scale = quantize_codes(w, bits, key, channel_axis=0)
    else:
        codes, scale = quantize_codes(w, bits, key)
        scale = jnp.full((w.shape[0], 1), scale)
    return PackedWeights(
        packed=pack_codes(codes, bits),
        scale=scale.reshape(1, -1).astype(jnp.float32),
        bits=bits,
        k_dim=w.shape[1],
        granularity=gran,
    )


def qmm(
    x: jax.Array,
    w: PackedWeights,
    *,
    w_t: Optional[PackedWeights] = None,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jax.Array:
    """y = x @ dequant(w)ᵀ.

    ``use_pallas=None`` auto-dispatches: the Mosaic kernel on TPU, the fused
    blocked jnp pipeline (:func:`qmm_fused`) elsewhere (interpret=True forces
    the kernel body on CPU for tests). Group-scaled weights
    (``granularity=per_block``) route to the group kernel, whose K blocks are
    additionally aligned to the scale group size. Block shapes default to
    :func:`select_block_config`'s problem-sized choice; explicit values are
    validated strictly (misalignment or pure-padding tiles raise). The
    per-tensor/per-channel kernel takes the codes and scales as stored and
    only x is padded to the blocks; the group kernel's operands are all padded.

    ``w_t`` optionally carries the same quantization packed in the transposed
    orientation (``pack_operator(shared=True)`` stores the pair anyway); the
    fused CPU path uses it to run batched calls as canonical-layout gemms.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu" or interpret
    if not use_pallas:
        return qmm_fused(x, w, w_t)
    m, k = x.shape
    n = w.packed.shape[0]
    if w.granularity.kind == "per_block":
        return _qmm_group(x, w, interpret, block_m, block_n, block_k)

    bm, bn, bk = select_block_config(m, n, w.k_dim, w.bits,
                                     block_m=block_m, block_n=block_n,
                                     block_k=block_k)
    mp, kp = _round_up(m, bm), _round_up(w.k_dim, bk)
    # only x is padded (zeros past K, which the codes past K meet): the kernel
    # reads the codes and scales as stored, with partial edge blocks. A named
    # scope on the copy, so a profiler trace can tell it from the kernel
    with jax.named_scope("qmm_pad"):
        x_p = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    y = qmm_pallas(x_p, w.packed, w.scale, bits=w.bits, k_dim=w.k_dim,
                   block_m=bm, block_n=bn, block_k=bk, interpret=interpret)
    return y[:m]


def _qmm_group(x, w: PackedWeights, interpret, block_m, block_n, block_k):
    """Group-scaled qmm dispatch: pad to blocks whose K size the scale groups
    tile exactly (padded codes are biased-zero, padded scale groups are 1.0 —
    both contribute nothing to the sliced-out output)."""
    g = w.granularity.group_size
    m, k = x.shape
    n = w.packed.shape[0]
    vpb = BY_BITS[w.bits].values_per_byte
    bm, bn, bk = select_block_config(m, n, w.k_dim, w.bits, group_size=g,
                                     block_m=block_m, block_n=block_n,
                                     block_k=block_k)
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(w.k_dim, bk)
    with jax.named_scope("qmm_pad"):
        x_p = jnp.pad(x, ((0, mp - m), (0, kp - k)))
        w_p = jnp.pad(w.packed, ((0, np_ - n), (0, kp // vpb - w.packed.shape[1])),
                      constant_values=_zero_byte(w.bits))
        s_p = jnp.pad(w.scale, ((0, np_ - n), (0, kp // g - w.scale.shape[1])),
                      constant_values=1.0)
    y = qmm_group_pallas(x_p, w_p, s_p, bits=w.bits, k_dim=kp, group_size=g,
                         block_m=bm, block_n=bn, block_k=bk, interpret=interpret)
    return y[:m, :n]


# ---------------------------------------------------------------------------
# Fused blocked pipeline for backends without Mosaic (CPU/GPU fallback).
#
# Two XLA:CPU pathologies make the naive oracle (unpack whole matrix → dot
# against wᵀ) slow: (a) a full-matrix uint8→f32 convert is write-bound on the
# (N, K) f32 temporary; (b) any matmul that is not a plain gemv / canonical
# row-major gemm falls off the fast library path (an `x @ w.T` transpose is a
# physical copy of Φ per application, ~100× at serving shapes). The fused
# path streams the *packed* codes block-by-block, unpacking each tile into a
# cache-resident f32 buffer, so the bytes that move from memory are the
# packed codes — the paper's bandwidth law. Three formulations, chosen
# statically from the problem shape:
#
# * M == 1  — multiply+reduce over N blocks (the only matvec formulation
#   XLA:CPU keeps vectorized when the matrix operand is an internal value).
# * M > 1 with shared transposed codes (``w_t``) — the batch-serving fast
#   path: the *other* orientation's packed array is the weight matrix already
#   transposed in memory, so each K-slab unpacks into a canonical row-major
#   (bk, N) tile and the contraction is an ordinary gemm accumulation. One
#   codes-stream serves all B rows per call.
# * M > 1 without ``w_t`` — minor×minor dot per N block (no transposes).
# ---------------------------------------------------------------------------

_FUSED_TILE_BYTES = 1 << 20    # target f32 dequant-tile footprint (cache-resident)


def _fused_tile_rows(rows: int, row_values: int) -> int:
    """Largest power-of-two row block whose f32 tile fits the target bytes."""
    cap = max(1, _FUSED_TILE_BYTES // max(4 * row_values, 1))
    b = 1
    while b * 2 <= cap:
        b *= 2
    return min(b, rows)


def _unpack_parts_f32(packed: jax.Array, bits: int) -> list[jax.Array]:
    """uint8 (..., Kp) → vpb arrays of f32 unit-scale codes, part-major.

    ``parts[i][..., j]`` is code ``j·vpb + i``; callers either interleave the
    parts (stack on a minor axis) or slice their x operand with the same
    stride so no interleave copy is needed."""
    fmt = BY_BITS[bits]
    k_half = jnp.float32(fmt.half_steps)
    if fmt.values_per_byte == 1:
        return [packed.astype(jnp.float32) - k_half]
    mask = jnp.uint8((1 << bits) - 1)
    return [((packed >> jnp.uint8(bits * i)) & mask).astype(jnp.float32) - k_half
            for i in range(fmt.values_per_byte)]


def _unpack_interleaved_f32(packed: jax.Array, bits: int) -> jax.Array:
    """uint8 (..., Kp) → (..., Kp·vpb) f32 unit-scale codes in storage order."""
    parts = _unpack_parts_f32(packed, bits)
    if len(parts) == 1:
        return parts[0]
    return jnp.stack(parts, axis=-1).reshape(
        *packed.shape[:-1], packed.shape[-1] * len(parts))


def _x_parts(x32: jax.Array, vpb: int, kp: int) -> list[jax.Array]:
    """Slice x (M, K) into the per-part operands matching _unpack_parts_f32:
    part i pairs with x columns i, i+vpb, …, zero-padded to length kp."""
    if vpb == 1:
        return [x32]
    m = x32.shape[0]
    return [jnp.pad(x32[:, i::vpb], ((0, 0), (0, kp - x32[:, i::vpb].shape[1])))
            for i in range(vpb)]


def _fused_matvec(x32: jax.Array, packed: jax.Array, bits: int, n: int) -> jax.Array:
    """M == 1: multiply+reduce over N blocks. Returns unit-scale (1, N)."""
    n_rows, kp = packed.shape
    vpb = BY_BITS[bits].values_per_byte
    xs = [xp[0] for xp in _x_parts(x32, vpb, kp)]
    bn = _fused_tile_rows(n_rows, kp * vpb)
    nb = _round_up(n_rows, bn) // bn
    if nb * bn != n_rows:
        packed = jnp.pad(packed, ((0, nb * bn - n_rows), (0, 0)),
                         constant_values=_zero_byte(bits))

    def block_y(p_blk):
        parts = _unpack_parts_f32(p_blk, bits)
        acc = jnp.sum(parts[0] * xs[0], axis=-1)
        for part, xv in zip(parts[1:], xs[1:]):
            acc = acc + jnp.sum(part * xv, axis=-1)
        return acc

    if nb == 1:
        return block_y(packed).reshape(1, nb * bn)[:, :n]
    _, ys = jax.lax.scan(lambda c, p_blk: (c, block_y(p_blk)), None,
                         packed.reshape(nb, bn, kp))
    return ys.reshape(1, nb * bn)[:, :n]


def _fused_batch_minor(x32: jax.Array, packed: jax.Array, bits: int, n: int) -> jax.Array:
    """M > 1, no transposed codes: minor×minor dot per N block. Unit scale."""
    m = x32.shape[0]
    n_rows, kp = packed.shape
    vpb = BY_BITS[bits].values_per_byte
    xps = _x_parts(x32, vpb, kp)
    bn = _fused_tile_rows(n_rows, kp * vpb)
    nb = _round_up(n_rows, bn) // bn
    if nb * bn != n_rows:
        packed = jnp.pad(packed, ((0, nb * bn - n_rows), (0, 0)),
                         constant_values=_zero_byte(bits))

    def block_y(p_blk):
        parts = _unpack_parts_f32(p_blk, bits)
        acc = jax.lax.dot_general(xps[0], parts[0], (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        for part, xp in zip(parts[1:], xps[1:]):
            acc = acc + jax.lax.dot_general(xp, part, (((1,), (1,)), ((), ())),
                                            preferred_element_type=jnp.float32)
        return acc

    if nb == 1:
        return block_y(packed)[:, :n]
    _, ys = jax.lax.scan(lambda c, p_blk: (c, block_y(p_blk)), None,
                         packed.reshape(nb, bn, kp))
    return jnp.moveaxis(ys, 0, 1).reshape(m, nb * bn)[:, :n]


def _fused_batch_canonical(x32: jax.Array, w_t: PackedWeights, n: int) -> jax.Array:
    """M > 1 with shared codes: ``w_t`` stores wᵀ's bytes, so each row slab
    unpacks straight into a canonical (bk, N) tile — gemm accumulation over
    K slabs, one packed stream amortized across the whole batch. Unit scale."""
    m, k = x32.shape
    k_rows, np_bytes = w_t.packed.shape
    bits = w_t.bits
    vpb = BY_BITS[bits].values_per_byte
    bk = _fused_tile_rows(k_rows, np_bytes * vpb)
    nbk = _round_up(k_rows, bk) // bk
    packed = w_t.packed
    if nbk * bk != k_rows:
        # padded K rows pair with zero-padded x columns: no contribution
        packed = jnp.pad(packed, ((0, nbk * bk - k_rows), (0, 0)),
                         constant_values=_zero_byte(bits))
        x32 = jnp.pad(x32, ((0, 0), (0, nbk * bk - k)))

    def tile(p_blk):
        return _unpack_interleaved_f32(p_blk, bits)     # (bk, N_padded) canonical

    if nbk == 1:
        return jax.lax.dot_general(x32, tile(packed), (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)[:, :n]
    x_blocks = jnp.moveaxis(x32.reshape(m, nbk, bk), 1, 0)  # (nbk, m, bk)

    def step(acc, blk):
        p_blk, x_blk = blk
        return acc + jax.lax.dot_general(x_blk, tile(p_blk), (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32), None

    acc0 = jnp.zeros((m, np_bytes * vpb), jnp.float32)
    y, _ = jax.lax.scan(step, acc0, (packed.reshape(nbk, bk, np_bytes), x_blocks))
    return y[:, :n]


def _fused_per_block(x32: jax.Array, w: PackedWeights) -> jax.Array:
    """Group-scaled fused path: the scale varies along K, so each tile is
    dequantized in full (codes × expanded scale) before its dot."""
    m, k = x32.shape
    n, kp = w.packed.shape
    g = w.granularity.group_size
    inv_half = 1.0 / BY_BITS[w.bits].half_steps
    bn = _fused_tile_rows(n, kp * BY_BITS[w.bits].values_per_byte)
    nb = _round_up(n, bn) // bn
    packed, scale = w.packed, w.scale
    if nb * bn != n:
        packed = jnp.pad(packed, ((0, nb * bn - n), (0, 0)),
                         constant_values=_zero_byte(w.bits))
        scale = jnp.pad(scale, ((0, nb * bn - n), (0, 0)), constant_values=1.0)

    def block_y(p_blk, s_blk):
        wt = (_unpack_interleaved_f32(p_blk, w.bits)[:, :k]
              * (expand_block_scale(s_blk, g, k) * inv_half))
        return jax.lax.dot_general(x32, wt, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    if nb == 1:
        return block_y(packed, scale)[:, :n]
    _, ys = jax.lax.scan(lambda c, blk: (c, block_y(*blk)), None,
                         (packed.reshape(nb, bn, kp),
                          scale.reshape(nb, bn, scale.shape[1])))
    return jnp.moveaxis(ys, 0, 1).reshape(m, nb * bn)[:, :n]


def qmm_fused(
    x: jax.Array,
    w: PackedWeights,
    w_t: Optional[PackedWeights] = None,
) -> jax.Array:
    """Fused unpack→dequant→dot on cache-resident tiles. Returns (M, N) f32.

    ``w_t``, when given, must hold the SAME quantization's codes packed in the
    transposed orientation (``pack_operator(shared=True)`` stores exactly that
    pair); it unlocks the canonical-layout batch gemm. Handles every scale
    granularity and batched x — B rows share one pass over the packed codes,
    which is what amortizes the stream across a batch."""
    m, k = x.shape
    n = w.packed.shape[0]
    if k != w.k_dim:
        raise ValueError(f"x K dim {k} != packed k_dim {w.k_dim}")
    x32 = x.astype(jnp.float32)
    if w.granularity.kind == "per_block":
        return _fused_per_block(x32, w)
    if m == 1:
        y = _fused_matvec(x32, w.packed, w.bits, n)
    elif w_t is not None and w.granularity.is_per_tensor:
        y = _fused_batch_canonical(x32, w_t, n)
    else:
        y = _fused_batch_minor(x32, w.packed, w.bits, n)
    return y * (w.scale.reshape(1, -1) / BY_BITS[w.bits].half_steps)


def _zero_byte(bits: int) -> int:
    """uint8 word whose every packed code is 0 (biased representation of 0)."""
    fmt = BY_BITS[bits]
    k = fmt.half_steps
    word = 0
    for i in range(fmt.values_per_byte):
        word |= k << (bits * i)
    return word


class PackedOperator(NamedTuple):
    """A quantized CS measurement matrix in both orientations.

    ``fwd``  computes Φ̂ x  (stores Φ̂ as (M, N) packed along N),
    ``adj``  computes Φ̂† r (stores Φ̂ᵀ* as (N, M) packed along M).
    Complex matrices store stacked real/imag parts with a leading axis of 2.
    """

    fwd_re: PackedWeights
    fwd_im: Optional[PackedWeights]
    adj_re: PackedWeights
    adj_im: Optional[PackedWeights]

    @property
    def is_complex(self) -> bool:
        return self.fwd_im is not None

    @property
    def nbytes(self) -> int:
        total = self.fwd_re.nbytes + self.adj_re.nbytes
        if self.is_complex:
            total += self.fwd_im.nbytes + self.adj_im.nbytes
        return total

    @property
    def scale_nbytes(self) -> int:
        """f32 scale bytes riding alongside the packed codes (the documented
        per-block overhead; per-tensor/per-channel carry (1, N) as before)."""
        total = self.fwd_re.scale_nbytes + self.adj_re.scale_nbytes
        if self.is_complex:
            total += self.fwd_im.scale_nbytes + self.adj_im.scale_nbytes
        return total


def _pack_from_codes(codes: jax.Array, scale: jax.Array, bits: int) -> PackedWeights:
    """Build PackedWeights from pre-quantized (N, K) int codes + scalar scale."""
    return PackedWeights(
        packed=pack_codes(codes, bits),
        scale=jnp.full((1, codes.shape[0]), scale, jnp.float32),
        bits=bits,
        k_dim=codes.shape[1],
    )


def pack_operator(
    phi: jax.Array,
    bits: int,
    key: Optional[jax.Array] = None,
    per_channel: bool = False,
    shared: bool = False,
    granularity: Union[Granularity, str, None] = None,
) -> PackedOperator:
    """Quantize a dense (M, N) measurement matrix for streaming IHT.

    Per-tensor scale by default (faithful to the paper's single c_Φ);
    ``granularity`` selects per_channel / per_block(g) scaling instead
    (overriding the legacy ``per_channel`` bool).

    ``shared=False`` draws an *independent* quantization for each orientation
    (Algorithm 1's Φ̂_{2n-1}/Φ̂_{2n} pairing, unbiased in expectation with a
    key). ``shared=True`` quantizes **once** — the same codes back both Φ̂ and
    Φ̂†, which is what a deployed ``requantize="fixed"`` system streaming
    pre-quantized data does, and makes the adjoint identity ⟨Φ̂x, r⟩ = ⟨x, Φ̂†r⟩
    exact. Shared codes match ``fake_quantize(phi, bits, key)`` bit-for-bit.

    Sharing is only possible with ONE scale per tensor: a per-channel or
    per-block scale is tied to an orientation's own row/contraction axis, so
    the transposed orientation cannot reuse the codes (its scale groups run
    across the other axis). Per-orientation scales therefore require
    ``shared=False``.
    """
    gran = _resolve_granularity(granularity, per_channel)
    if shared and not gran.is_per_tensor:
        raise ValueError(
            f"pack_operator(shared=True) streams ONE per-tensor quantization "
            f"through both orientations; a {gran} scale is tied to each "
            f"orientation's own axes, so shared codes cannot carry it. Pass "
            f"shared=False (per-orientation quantization, adjoint identity "
            f"approximate) or granularity='per_tensor' (exact shared codes).")
    if shared:
        q = quantize(phi, bits, key)
        if q.is_complex:
            cre, cim = q.codes[0], q.codes[1]
            return PackedOperator(
                fwd_re=_pack_from_codes(cre, q.scale, bits),
                fwd_im=_pack_from_codes(cim, q.scale, bits),
                adj_re=_pack_from_codes(cre.T, q.scale, bits),
                adj_im=_pack_from_codes(cim.T, q.scale, bits),
            )
        return PackedOperator(
            fwd_re=_pack_from_codes(q.codes, q.scale, bits),
            fwd_im=None,
            adj_re=_pack_from_codes(q.codes.T, q.scale, bits),
            adj_im=None,
        )
    if jnp.iscomplexobj(phi):
        re, im = jnp.real(phi), jnp.imag(phi)
        keys = jax.random.split(key, 4) if key is not None else [None] * 4
        return PackedOperator(
            fwd_re=pack_weights(re, bits, keys[0], granularity=gran),
            fwd_im=pack_weights(im, bits, keys[1], granularity=gran),
            adj_re=pack_weights(re.T, bits, keys[2], granularity=gran),
            adj_im=pack_weights(im.T, bits, keys[3], granularity=gran),
        )
    keys = jax.random.split(key, 2) if key is not None else [None, None]
    return PackedOperator(
        fwd_re=pack_weights(phi, bits, keys[0], granularity=gran),
        fwd_im=None,
        adj_re=pack_weights(phi.T, bits, keys[1], granularity=gran),
        adj_im=None,
    )


def packed_matvec(op: PackedOperator, x: jax.Array, shared: bool = False, **kw) -> jax.Array:
    """Φ̂ x for real or complex Φ̂ (x may be real or complex).

    ``x`` is a single vector (N,) or a batch (B, N); a batch is served by ONE
    kernel invocation per real matmul, amortizing the packed Φ̂ stream over B.
    ``shared=True`` asserts the operator was built with
    ``pack_operator(shared=True)`` (adjoint bytes == forward bytes transposed),
    letting batched calls borrow the other orientation as a pre-transposed
    canonical layout. Never pass it for independently quantized orientations.
    """
    single = x.ndim == 1
    xb = x[None, :] if single else x
    if not op.is_complex:
        out = qmm(xb.astype(jnp.float32), op.fwd_re,
                  w_t=op.adj_re if shared else None, **kw)
        return out[0] if single else out
    xr = jnp.real(xb).astype(jnp.float32)
    rr = qmm(xr, op.fwd_re, w_t=op.adj_re if shared else None, **kw)
    ir = qmm(xr, op.fwd_im, w_t=op.adj_im if shared else None, **kw)
    if not jnp.iscomplexobj(x):
        # real input (e.g. a real sky through complex Φ̂): the imaginary-part
        # products are identically zero — skip their kernel calls so the packed
        # matrices stream once, not twice.
        out = jax.lax.complex(rr, ir)
        return out[0] if single else out
    xi = jnp.imag(xb).astype(jnp.float32)
    ri = qmm(xi, op.fwd_re, w_t=op.adj_re if shared else None, **kw)
    ii = qmm(xi, op.fwd_im, w_t=op.adj_im if shared else None, **kw)
    out = jax.lax.complex(rr - ii, ri + ir)
    return out[0] if single else out


def packed_rmatvec(op: PackedOperator, r: jax.Array, shared: bool = False, **kw) -> jax.Array:
    """Φ̂† r (conjugate transpose) for real or complex Φ̂; (M,) or batched (B, M).

    ``shared`` as in :func:`packed_matvec` (here the *forward* bytes serve as
    the adjoint's pre-transposed canonical layout)."""
    single = r.ndim == 1
    rb = r[None, :] if single else r
    if not op.is_complex:
        out = qmm(rb.astype(jnp.float32), op.adj_re,
                  w_t=op.fwd_re if shared else None, **kw)
        return out[0] if single else out
    # Φ† = (Re − j·Im)ᵀ ; (Φ† r) = (Reᵀ r_re + Imᵀ r_im) + j(Reᵀ r_im − Imᵀ r_re)
    rr_ = jnp.real(rb).astype(jnp.float32)
    t1 = qmm(rr_, op.adj_re, w_t=op.fwd_re if shared else None, **kw)
    t4 = qmm(rr_, op.adj_im, w_t=op.fwd_im if shared else None, **kw)
    if not jnp.iscomplexobj(r):
        out = jax.lax.complex(t1, -t4)
        return out[0] if single else out
    ri_ = jnp.imag(rb).astype(jnp.float32)
    t2 = qmm(ri_, op.adj_im, w_t=op.fwd_im if shared else None, **kw)
    t3 = qmm(ri_, op.adj_re, w_t=op.fwd_re if shared else None, **kw)
    out = jax.lax.complex(t1 + t2, t3 - t4)
    return out[0] if single else out
