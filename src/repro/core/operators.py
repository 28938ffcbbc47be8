"""Linear-operator backends for the (Q)NIHT hot loop.

Blumensath & Davies' IHT analysis only ever touches the sensing matrix through
``Φ̂ x`` / ``Φ̂† r`` products, so the solver needs nothing but a matvec pair —
that is the seam that lets one NIHT loop run on three physically different
representations of Φ̂:

* :class:`DenseOperator`        — f32/c64 matrix, XLA dot. Full precision, and
  also the ``requantize="fixed"`` *fake-quantized* carrier (quantized values
  stored as dense floats: same math as deployment, same bytes as f32).
* :class:`FakeQuantPairOperator`— the per-iteration fresh pair
  (Φ̂_{2n-1}, Φ̂_{2n}) of Algorithm 1's ``requantize="pair"`` mode, each member
  a fake-quantized :class:`DenseOperator`.
* :class:`PackedStreamingOperator` — packed uint8 codes streamed through the
  Pallas ``qmm`` kernels: 4/8/16× fewer operator bytes per application at
  8/4/2 bits. The paper's systems claim (`T = size(Φ̂)/bandwidth`, suppl. §8.1)
  lives here.
* :class:`SubsampledFourierOperator` — *matrix-free* Φ: an implicit 2D FFT
  followed by a k-space sampling mask (the MRI workload, paper §5's brain
  images). No (M, N) array ever exists — at 256×256 the dense partial-Fourier
  matrix would be ~2 GB; the implicit form stores only the sample indices and
  their inverse map, so both the sampling (``mv``) and the zero-fill (``rmv``)
  are gathers.
* :class:`WaveletSynthesisOperator` — the orthonormal synthesis W† mapping
  wavelet coefficients to image pixels (implicit multi-level DWT, see
  :mod:`repro.transforms.wavelet`).
* :class:`ComposedOperator` — the algebra: ``B ∘ A`` with the exact adjoint
  ``A† ∘ B†``. Composing the two above yields the full CS-MRI model
  Φ = P_Ω F W†, still matrix-free.

Operator protocol (the contract every backend implements, and what a new
operator must provide to slot into ``qniht``/``qniht_batch``/
``qniht_batch_sharded`` — ``docs/operator-protocol.md`` walks through writing
one):

* ``mv(x)`` — apply Φ̂: ``(n,) → (m,)``, and batched ``(B, n) → (B, m)``. A
  batch MUST be served by one vectorized application (one matmul / kernel
  call / batched FFT), since amortizing the operator stream across B problems
  is the "heavy traffic" scenario ``qniht_batch`` exploits.
* ``rmv(r)`` — apply the adjoint Φ̂†: ``(m,) → (n,)``, batched likewise.
  **Adjoint contract**: ``⟨mv(x), r⟩ == ⟨x, rmv(r)⟩`` must hold exactly (to
  float tolerance) — NIHT's step size µ = ‖g_Γ‖²/‖Φ̂ g_Γ‖² and its acceptance
  test both assume Φ̂† is the true adjoint, and a systematic mismatch breaks
  the monotone-descent guarantee. Quantized backends are the one sanctioned
  relaxation: per-orientation scales hold the identity only to within
  quantization error (documented on :class:`PackedStreamingOperator`).
* ``shape`` — ``(m, n)`` as ints; ``dtype`` — the measurement dtype (what
  ``mv`` returns).
* ``nbytes`` — bytes of operator data streamed by ONE application (mv ≈ rmv):
  the quantity the paper's bandwidth model ``T = size(Φ̂)/BW`` (suppl. §8.1)
  prices. Dense: the full matrix. Packed: the packed codes (+ documented
  ``scale_nbytes``). Matrix-free: only the parameters actually read — the
  sampling pattern for P_Ω F, the filter taps for W†. Composition sums the
  factors' nbytes (each factor's data is streamed once per application).

Operators are registered pytrees (config in aux_data) so they both close over
``lax.scan`` bodies and cross jit boundaries as arguments —
``qniht(phi_op, y, ...)`` takes any of them directly. Composition preserves
this: a :class:`ComposedOperator` of pytree operators is a pytree.

:func:`make_iteration_operators` is the solver's factory seam: it turns
whatever the caller handed in (dense array or operator) plus the
``bits_phi``/``requantize``/``backend`` knobs into the per-iteration
(gradient, residual) operator pair Algorithm 1 consumes.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.qmm.ops import (
    PackedOperator,
    pack_operator,
    packed_matvec,
    packed_rmatvec,
)
from repro.quant.formats import Granularity, as_granularity
from repro.quant.quantize import fake_quantize


@jax.tree_util.register_pytree_node_class
class DenseOperator:
    """Φ̂ as a dense (m, n) array; streams itemsize bytes/entry per application."""

    def __init__(self, mat: jax.Array):
        self.mat = mat

    @property
    def shape(self):
        return self.mat.shape

    @property
    def dtype(self):
        return self.mat.dtype

    @property
    def nbytes(self) -> int:
        return self.mat.size * self.mat.dtype.itemsize

    # Full f32 products on every backend: the TPU's default matmul precision
    # rounds f32 operands to bf16, which would make the f32 baseline a bf16 one.
    def mv(self, x: jax.Array) -> jax.Array:
        # Contract x's minor axis against mat's minor axis directly: `x @ mat.T`
        # makes XLA:CPU materialize the transpose as a physical copy of Φ every
        # application (~100× at serving shapes).
        m = self.mat
        dt = jnp.result_type(x.dtype, m.dtype)
        return jax.lax.dot_general(
            x.astype(dt), m.astype(dt),
            (((x.ndim - 1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
        )

    def rmv(self, r: jax.Array) -> jax.Array:
        m = self.mat
        return jnp.matmul(r, jnp.conj(m) if jnp.iscomplexobj(m) else m,
                          precision=jax.lax.Precision.HIGHEST)

    def tree_flatten(self):
        return (self.mat,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


@jax.tree_util.register_pytree_node_class
class FakeQuantPairOperator:
    """Algorithm 1's fresh stochastic pair (Φ̂_{2n-1}, Φ̂_{2n}) per iteration.

    ``at_iteration(i)`` fake-quantizes Φ twice with iteration-folded keys and
    returns the (gradient, residual) operators. Compute and traffic are dense
    f32 — this backend models the paper's *statistical* algorithm, not the
    deployed streaming system (that is :class:`PackedStreamingOperator`).
    """

    def __init__(self, phi: jax.Array, bits: int, key: jax.Array):
        self.phi = phi
        self.bits = int(bits)
        self.key = key

    @property
    def shape(self):
        return self.phi.shape

    @property
    def dtype(self):
        return self.phi.dtype

    @property
    def nbytes(self) -> int:
        return self.phi.size * self.phi.dtype.itemsize

    def at_iteration(self, i: jax.Array) -> tuple[DenseOperator, DenseOperator]:
        k1 = jax.random.fold_in(self.key, 2 * i)
        k2 = jax.random.fold_in(self.key, 2 * i + 1)
        return (
            DenseOperator(fake_quantize(self.phi, self.bits, k1)),
            DenseOperator(fake_quantize(self.phi, self.bits, k2)),
        )

    def tree_flatten(self):
        return (self.phi, self.key), (self.bits,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        phi, key = children
        return cls(phi, aux[0], key)


@jax.tree_util.register_pytree_node_class
class PackedStreamingOperator:
    """Φ̂ as packed uint8 codes, applied via the Pallas ``qmm`` kernels.

    With the default ``per_tensor`` granularity both orientations are packed
    ONCE (shared codes — the same quantized data a fixed-precision system
    streams), so every NIHT iteration moves ``bits/32`` of the f32 bytes.
    ``per_channel``/``per_block`` granularities scale each orientation along
    its own axes, so each is quantized separately (shared codes cannot carry
    orientation-local scales — see :func:`repro.kernels.qmm.ops.pack_operator`);
    the adjoint identity then holds to within quantization error and the f32
    scale vectors add ``scale_nbytes`` of (documented) stream overhead.
    ``interpret``/``use_pallas`` plumb through to the kernel dispatch (pure-jnp
    oracle off-TPU).
    """

    def __init__(self, packed: PackedOperator, use_pallas: Optional[bool] = None,
                 interpret: bool = False, shared: bool = False):
        self.packed = packed
        self.use_pallas = use_pallas
        self.interpret = bool(interpret)
        # True iff `packed` came from pack_operator(shared=True): the adjoint's
        # bytes are then the forward codes transposed, which the fused CPU path
        # exploits as a pre-transposed canonical layout for batched calls.
        self.shared = bool(shared)

    @classmethod
    def pack(cls, phi: jax.Array, bits: int, key: Optional[jax.Array] = None,
             granularity=None, **kw) -> "PackedStreamingOperator":
        """Quantize + pack Φ. Per-tensor granularity (default) shares one set
        of codes across both orientations (matches ``fake_quantize(phi, bits,
        key)`` bit-for-bit); group granularities quantize per orientation."""
        gran = as_granularity(granularity)
        if gran.is_per_tensor:
            return cls(pack_operator(phi, bits, key, shared=True), shared=True, **kw)
        return cls(pack_operator(phi, bits, key, shared=False, granularity=gran), **kw)

    @property
    def bits(self) -> int:
        return self.packed.fwd_re.bits

    @property
    def granularity(self) -> Granularity:
        return self.packed.fwd_re.granularity

    @property
    def scale_nbytes(self) -> int:
        """f32 scale bytes streamed per application (fwd orientation)."""
        n = self.packed.fwd_re.scale_nbytes
        if self.packed.is_complex:
            n += self.packed.fwd_im.scale_nbytes
        return n

    @property
    def shape(self):
        return (self.packed.fwd_re.packed.shape[0], self.packed.adj_re.packed.shape[0])

    @property
    def dtype(self):
        return jnp.complex64 if self.packed.is_complex else jnp.float32

    @property
    def nbytes(self) -> int:
        n = self.packed.fwd_re.nbytes
        if self.packed.is_complex:
            n += self.packed.fwd_im.nbytes
        return n

    def mv(self, x: jax.Array) -> jax.Array:
        return packed_matvec(self.packed, x, shared=self.shared,
                             use_pallas=self.use_pallas, interpret=self.interpret)

    def rmv(self, r: jax.Array) -> jax.Array:
        return packed_rmatvec(self.packed, r, shared=self.shared,
                              use_pallas=self.use_pallas, interpret=self.interpret)

    def tree_flatten(self):
        return (self.packed,), (self.use_pallas, self.interpret, self.shared)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


@jax.tree_util.register_pytree_node_class
class SubsampledFourierOperator:
    """Matrix-free Φ = P_Ω F: orthonormal 2D DFT of an r×r image, subsampled at
    the k-space positions Ω (the MRI acquisition model, paper §5).

    ``mv`` is ``fft2(norm="ortho")`` + gather at the flat sample indices;
    ``rmv`` is the exact adjoint (F is unitary, so (P_Ω F)† = F† P_Ωᵀ): the
    zero-fill P_Ωᵀ is a gather too, of ``v`` extended by one zero slot, through
    the inverse map (grid position → its place in ``indices``, or the zero
    slot), then ``ifft2(norm="ortho")``. It equals a zero-fill scatter bit for
    bit. Nothing of size M×N is ever built — ``nbytes`` counts only the stored
    sampling pattern (int32 indices, the 1-bit/pixel mask an acquisition
    system would keep, and the int32 inverse map), which is why a 256×256
    problem (dense Φ ≈ 2 GB complex64) costs ~360 KB here.

    Build from a boolean k-space mask with :meth:`from_mask` (concrete, outside
    jit — the sample count M becomes the static output shape). The constructor
    builds the inverse map on the host from the concrete ``indices``.
    """

    def __init__(self, indices: jax.Array, resolution: int):
        self.indices = indices          # (M,) int32, flat positions in the r×r grid
        self.resolution = int(resolution)
        # each grid position's place in `indices`, or M: the zero slot rmv appends
        idx = np.asarray(indices)
        inv = np.full(self.resolution**2, idx.shape[0], np.int32)
        inv[idx] = np.arange(idx.shape[0], dtype=np.int32)
        self.inverse = jnp.asarray(inv, jnp.int32)  # (r²,)

    @classmethod
    def from_mask(cls, mask) -> "SubsampledFourierOperator":
        m = np.asarray(mask, bool)
        if m.ndim != 2 or m.shape[0] != m.shape[1]:
            raise ValueError(f"mask must be a square (r, r) boolean array, got {m.shape}")
        if not m.any():
            raise ValueError("empty sampling mask: no k-space positions selected")
        return cls(jnp.asarray(np.flatnonzero(m.ravel()), jnp.int32), m.shape[0])

    @property
    def shape(self):
        return (self.indices.shape[0], self.resolution * self.resolution)

    @property
    def dtype(self):
        return jnp.dtype(jnp.complex64)

    @property
    def nbytes(self) -> int:
        # sampling pattern only: int32 sample indices + the packed boolean mask
        # + the int32 inverse map the zero-fill gathers through
        n = self.resolution**2
        return self.indices.shape[0] * 4 + math.ceil(n / 8) + n * 4

    def mask(self) -> jax.Array:
        """(r, r) boolean k-space sampling mask (recomputed from the indices)."""
        r = self.resolution
        return jnp.zeros((r * r,), bool).at[self.indices].set(True).reshape(r, r)

    @property
    def kspace_op(self) -> "SubsampledFourierOperator":
        """The factor owning the k-space sampling geometry (self). Exists so
        band-geometry consumers (``kspace_radial_bands``) can unwrap either a
        bare Fourier operator or a composition uniformly."""
        return self

    # named scopes "fft" and "sample" label the transform and the k-space
    # gathers in a profiler trace (metadata only)
    def mv(self, x: jax.Array) -> jax.Array:
        r = self.resolution
        img = x.reshape(*x.shape[:-1], r, r)
        with jax.named_scope("fft"):
            k = jnp.fft.fft2(img, norm="ortho").astype(self.dtype)
        with jax.named_scope("sample"):
            return jnp.take(k.reshape(*x.shape[:-1], r * r), self.indices, axis=-1)

    def rmv(self, v: jax.Array) -> jax.Array:
        r = self.resolution
        with jax.named_scope("sample"):
            v = v.astype(self.dtype)
            padded = jnp.concatenate([v, jnp.zeros((*v.shape[:-1], 1), self.dtype)], -1)
            full = jnp.take(padded, self.inverse, axis=-1, mode="clip")
        with jax.named_scope("fft"):
            img = jnp.fft.ifft2(full.reshape(*v.shape[:-1], r, r), norm="ortho")
        return img.reshape(*v.shape[:-1], r * r).astype(self.dtype)

    def tree_flatten(self):
        return (self.indices, self.inverse), (self.resolution,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        # past __init__, which builds the map from concrete indices:
        # transformations unflatten with placeholder leaves, None among them
        obj = object.__new__(cls)
        obj.indices, obj.inverse = children
        obj.resolution = aux[0]
        return obj


@jax.tree_util.register_pytree_node_class
class WaveletSynthesisOperator:
    """Matrix-free orthonormal wavelet synthesis W†: coefficients → image.

    ``mv(c)`` runs the inverse multi-level periodized 2D DWT
    (:func:`repro.transforms.wavelet.idwt2`) on the ``(r²,)`` coefficient
    vector; W is unitary, so ``rmv`` — the exact adjoint (W†)† = W — is simply
    the *forward* transform. Square (r², r²) and real, but applied to complex
    residuals component-wise (the transform is linear over ℂ), which is what
    the composed CS-MRI adjoint W F† P_Ωᵀ feeds it.

    ``nbytes`` counts the only operator data an application reads: the 2·L
    f32 filter taps — the reason a transform-domain Φ costs nothing over the
    pixel-domain one on the stream model.
    """

    def __init__(self, resolution: int, wavelet: str = "haar",
                 levels: Optional[int] = None):
        from repro.transforms.wavelet import _resolve_levels, wavelet_filters

        self.resolution = int(resolution)
        self.wavelet = str(wavelet)
        wavelet_filters(self.wavelet)  # validate the spelling eagerly
        self.levels = _resolve_levels(self.resolution, self.wavelet, levels)

    @property
    def shape(self):
        n = self.resolution * self.resolution
        return (n, n)

    @property
    def dtype(self):
        return jnp.dtype(jnp.float32)

    @property
    def nbytes(self) -> int:
        from repro.transforms.wavelet import wavelet_filters

        lo, hi = wavelet_filters(self.wavelet)
        return 4 * (len(lo) + len(hi))

    def mv(self, c: jax.Array) -> jax.Array:
        from repro.transforms.wavelet import idwt2

        r = self.resolution
        with jax.named_scope("dwt"):
            img = idwt2(c.reshape(*c.shape[:-1], r, r), self.wavelet, self.levels)
        return img.reshape(*c.shape[:-1], r * r)

    def rmv(self, x: jax.Array) -> jax.Array:
        from repro.transforms.wavelet import dwt2

        r = self.resolution
        with jax.named_scope("dwt"):
            co = dwt2(x.reshape(*x.shape[:-1], r, r), self.wavelet, self.levels)
        return co.reshape(*x.shape[:-1], r * r)

    def tree_flatten(self):
        return (), (self.resolution, self.wavelet, self.levels)

    @classmethod
    def tree_unflatten(cls, aux, children):
        del children
        return cls(*aux)


@jax.tree_util.register_pytree_node_class
class ComposedOperator:
    """The operator algebra's product: ``ComposedOperator(B, A)`` applies
    x ↦ B(A x), with the exact adjoint r ↦ A†(B† r).

    Exactness is compositional — if each factor satisfies the adjoint
    contract, so does the product: ⟨BAx, r⟩ = ⟨Ax, B†r⟩ = ⟨x, A†B†r⟩. Shapes
    must chain (``B.shape[1] == A.shape[0]``); ``shape`` is
    (B.shape[0], A.shape[1]), ``dtype`` is the outer factor's measurement
    dtype, and ``nbytes`` is the sum of the factors' (each factor's data is
    streamed once per application).

    The CS-MRI model Φ = P_Ω F W† is
    ``ComposedOperator(SubsampledFourierOperator, WaveletSynthesisOperator)``;
    the ``kspace_op`` property surfaces whichever factor owns the k-space
    sampling geometry so per-band observation quantization keeps working on
    the composition.
    """

    def __init__(self, outer, inner):
        if outer.shape[1] != inner.shape[0]:
            raise ValueError(
                f"cannot compose: outer expects inputs of size {outer.shape[1]}, "
                f"inner produces size {inner.shape[0]}")
        self.outer = outer
        self.inner = inner

    @property
    def shape(self):
        return (self.outer.shape[0], self.inner.shape[1])

    @property
    def dtype(self):
        return self.outer.dtype

    @property
    def nbytes(self) -> int:
        return self.outer.nbytes + self.inner.nbytes

    @property
    def kspace_op(self):
        """The (unique) factor exposing k-space geometry, unwrapped through
        nested compositions."""
        for f in (self.outer, self.inner):
            op = getattr(f, "kspace_op", None)
            if op is not None:
                return op
        raise AttributeError("no factor of this composition owns k-space geometry")

    def mv(self, x: jax.Array) -> jax.Array:
        return self.outer.mv(self.inner.mv(x))

    def rmv(self, r: jax.Array) -> jax.Array:
        return self.inner.rmv(self.outer.rmv(r))

    def tree_flatten(self):
        return (self.outer, self.inner), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


def is_linear_operator(phi) -> bool:
    """True when ``phi`` follows the operator protocol rather than being a
    dense array (ndarray-likes expose ``mv``/``rmv`` never, operators always)."""
    return hasattr(phi, "mv") and hasattr(phi, "rmv")


def as_operator(phi):
    """Dense (M, N) array → :class:`DenseOperator`; operators pass through."""
    return phi if is_linear_operator(phi) else DenseOperator(phi)


def make_iteration_operators(phi, bits_phi, requantize, backend, key,
                             granularity=None):
    """The solver's backend/requantize factory seam.

    Maps the caller's Φ — dense array or operator — plus the quantization knobs
    onto ``(phi_true, get_ops)`` where ``phi_true`` applies full-precision Φ
    (for true-residual traces) and ``get_ops(i)`` yields the (gradient,
    residual) operator pair Algorithm 1 uses at iteration ``i``.

    Dense arrays reproduce the historical dispatch (and its key folding)
    bit-for-bit — ``granularity`` (per_tensor default) only reaches the packed
    backend, where non-per-tensor scales switch the pack to per-orientation
    group-scaled codes. Operator inputs are matrix-free: they are used as-is
    for every iteration — any quantization of the operator's data is the
    operator's own representation choice, so ``bits_phi``/``backend`` must be
    left at their defaults (enforced in the solver's validation).
    """
    if is_linear_operator(phi):
        return phi, lambda i: (phi, phi)
    phi_true = DenseOperator(phi)
    if backend == "packed":
        op = PackedStreamingOperator.pack(phi, bits_phi, jax.random.fold_in(key, 0),
                                          granularity=granularity)
        return phi_true, lambda i: (op, op)
    if bits_phi and requantize == "pair":
        return phi_true, FakeQuantPairOperator(phi, bits_phi, key).at_iteration
    if bits_phi:
        op = DenseOperator(fake_quantize(phi, bits_phi, jax.random.fold_in(key, 0)))
        return phi_true, lambda i: (op, op)
    return phi_true, lambda i: (phi_true, phi_true)
