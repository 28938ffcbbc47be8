"""Pallas qmm kernel: interpret-mode allclose sweeps against the ref.py oracle,
its exactness against a float64 reference, the fused CPU path's parity sweep
and the block-config selector rules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_shim import given, settings, st

from repro.kernels.qmm.kernel import (
    VMEM_BUDGET_BYTES,
    qmm_pallas,
    select_block_config,
    split_bf16x3,
    vmem_bytes,
)
from repro.kernels.qmm.ops import (
    PackedWeights,
    _zero_byte,
    pack_operator,
    pack_weights,
    packed_matvec,
    packed_rmatvec,
    qmm,
)
from repro.kernels.qmm.ref import qmm_group_ref, qmm_ref
from repro.quant import fake_quantize
from repro.quant.formats import BY_BITS, PER_CHANNEL, per_block
from repro.quant.pack import pack_codes, packed_len

BITS = [2, 4, 8]


class TestQmmVsOracle:
    @given(
        bits=st.sampled_from(BITS),
        m=st.integers(1, 40),
        k=st.integers(1, 300),
        n=st.integers(1, 150),
        seed=st.integers(0, 1000),
    )
    @settings(max_examples=25, deadline=None)
    def test_shape_sweep(self, bits, m, k, n, seed):
        key = jax.random.PRNGKey(seed)
        x = jax.random.normal(key, (m, k), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (n, k), jnp.float32)
        pw = pack_weights(w, bits, jax.random.fold_in(key, 2))
        ref = qmm_ref(x, pw.packed, pw.scale, bits, k)
        out = qmm(x, pw, use_pallas=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("bits", BITS)
    @pytest.mark.slow
    def test_dtype_sweep(self, dtype, bits):
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (16, 256)).astype(dtype)
        w = jax.random.normal(jax.random.fold_in(key, 1), (64, 256), jnp.float32)
        pw = pack_weights(w, bits, jax.random.fold_in(key, 2))
        ref = qmm_ref(x, pw.packed, pw.scale, bits, 256)
        out = qmm(x, pw, use_pallas=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-2, atol=1e-2)

    @pytest.mark.parametrize("bits", BITS)
    @pytest.mark.slow
    def test_exact_block_multiple_shapes(self, bits):
        key = jax.random.PRNGKey(1)
        x = jax.random.normal(key, (128, 512), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (128, 512), jnp.float32)
        pw = pack_weights(w, bits, jax.random.fold_in(key, 2))
        ref = qmm_ref(x, pw.packed, pw.scale, bits, 512)
        out = qmm(x, pw, use_pallas=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-4)


def _wide_f32(rng, shape, binades=40):
    """f32 values with random signs whose magnitudes span ``binades`` powers of 2."""
    mag = np.exp2(rng.uniform(-binades / 2, binades / 2, shape))
    return (rng.choice([-1.0, 1.0], shape) * rng.uniform(1, 2, shape) * mag
            ).astype(np.float32)


class TestExactness:
    """The kernel's one bf16 MXU pass is full f32 arithmetic: codes are exact
    in bf16, x = hi + mid + lo exactly, every product is exact and every sum
    accumulates in f32."""

    def test_split_reconstructs_every_binade(self):
        rng = np.random.default_rng(0)
        # every exponent of the documented range, random mantissas and signs
        expo = np.repeat(np.arange(-100, 127), 384)
        mant = rng.integers(0, 1 << 23, expo.size)
        x = (rng.choice([-1.0, 1.0], expo.size)
             * np.ldexp(1.0 + mant / 2.0 ** 23, expo)).astype(np.float32)
        x = np.concatenate([x, np.zeros(128, np.float32)]).reshape(-1, 128)
        parts = np.asarray(split_bf16x3(jnp.asarray(x, dtype=jnp.float32)))
        # each part is a bfloat16 exactly: the low 16 bits of its f32 are zero
        assert not (parts.view(np.uint32) & 0xFFFF).any()
        r = x.shape[0]
        hi, mid, lo = parts[:r], parts[r:2 * r], parts[2 * r:]
        recon = hi.astype(np.float64) + mid.astype(np.float64) + lo.astype(np.float64)
        np.testing.assert_array_equal(recon, x.astype(np.float64))

    @given(xs=st.lists(st.one_of(
        st.just(0.0),
        st.floats(min_value=2.0 ** -100, max_value=2.0 ** 126, width=32),
        st.floats(min_value=-(2.0 ** 126), max_value=-(2.0 ** -100), width=32)),
        min_size=1, max_size=64))
    @settings(max_examples=200, deadline=None)
    def test_split_reconstructs_any_f32(self, xs):
        x = np.asarray(xs, np.float32).reshape(1, -1)
        parts = np.asarray(split_bf16x3(jnp.asarray(x, dtype=jnp.float32)))
        assert not (parts.view(np.uint32) & 0xFFFF).any()
        parts = parts.astype(np.float64)
        np.testing.assert_array_equal(parts.sum(axis=0), x[0].astype(np.float64))

    # explicit blocks: several K steps; or K steps of two 512-lane chunks of
    # packed codes each (bit planes and chunk offsets must pair up)
    @pytest.mark.parametrize("blocks", ["auto", "ksteps", "chunks"])
    @pytest.mark.parametrize("gran", ["per_channel", "per_block"])
    @pytest.mark.parametrize("bits", BITS)
    def test_kernel_at_f32_accumulation_level(self, bits, gran, blocks):
        rng = np.random.default_rng(bits)
        fmt = BY_BITS[bits]
        m, n, k, g = 8, 256, 4096, 64
        codes = rng.integers(fmt.code_min, fmt.code_max + 1, (n, k))
        codes[0, :2] = fmt.code_min, fmt.code_max          # both ends of the range
        x = _wide_f32(rng, (m, k))
        if gran == "per_block":
            scale = np.exp2(rng.uniform(-4, 4, (n, k // g))).astype(np.float32)
            w_scale = np.repeat(scale, g, axis=1)
            granularity = per_block(g)
        else:
            scale = np.exp2(rng.uniform(-4, 4, (1, n))).astype(np.float32)
            w_scale = scale.T
            granularity = PER_CHANNEL
        pw = PackedWeights(packed=pack_codes(jnp.asarray(codes, jnp.int8), bits),
                           scale=jnp.asarray(scale, dtype=jnp.float32), bits=bits,
                           k_dim=k, granularity=granularity)
        bm, bn, bk = {"auto": (None, None, None), "ksteps": (8, 128, 1024),
                      "chunks": (8, 128, 1024 * fmt.values_per_byte)}[blocks]
        y = qmm(jnp.asarray(x, dtype=jnp.float32), pw, use_pallas=True,
                interpret=True, block_m=bm, block_n=bn, block_k=bk)
        y = np.asarray(y, np.float64)
        ref = x.astype(np.float64) @ (codes * w_scale.astype(np.float64)
                                      / fmt.half_steps).T
        assert np.linalg.norm(y - ref) <= 1e-6 * np.linalg.norm(ref)


def _padded_route(x, pw, bm, bn, bk, interpret=True):
    """The kernel over codes and scales padded out to block multiples
    (biased-zero codes, zero scales): the route that copied them every call."""
    m, k = x.shape
    n = pw.packed.shape[0]
    mp, np_ = -(-m // bm) * bm, -(-n // bn) * bn
    kp = -(-k // bk) * bk
    vpb = BY_BITS[pw.bits].values_per_byte
    x_p = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    w_p = jnp.pad(pw.packed, ((0, np_ - n), (0, kp // vpb - pw.packed.shape[1])),
                  constant_values=_zero_byte(pw.bits))
    s_p = jnp.pad(pw.scale, ((0, 0), (0, np_ - n)))
    y = qmm_pallas(x_p, w_p, s_p, bits=pw.bits, k_dim=kp, block_m=bm,
                   block_n=bn, block_k=bk, interpret=interpret)
    return y[:m, :n]


def _jaxpr_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in its equations."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _jaxpr_eqns(sub)


class TestRaggedEdges:
    """The per-tensor/per-channel kernel reads the codes and their scale row as
    stored: its partial last N and K blocks give exactly what the kernel gives
    over codes padded to block multiples. In interpret mode the blocks' lanes
    past the arrays read 0 bytes (codes of -half_steps) and NaN scales, so a
    code past K that met a nonzero x, or a scale past N that reached a written
    output, would show."""

    # (n, k, block_k): "n" — 300 rows over 128-row blocks; "k" — 870 codes
    # over several K steps of the least block; "k_wide" — one K block wider
    # than the 174 codes; "both" — LOFAR's ragged widths at the auto blocks
    CASES = {"n": (300, 1024, None), "k": (256, 870, "unit"),
             "k_wide": (256, 174, None), "both": (300, 870, None)}

    @pytest.mark.parametrize("edge", sorted(CASES))
    @pytest.mark.parametrize("bits", BITS)
    def test_ragged_blocks_bit_equal_padded_route(self, bits, edge):
        n, k, block_k = self.CASES[edge]
        if block_k == "unit":
            block_k = 128 * BY_BITS[bits].values_per_byte
        key = jax.random.PRNGKey(bits)
        x = jax.random.normal(key, (8, k), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (n, k), jnp.float32)
        pw = pack_weights(w, bits, jax.random.fold_in(key, 2))
        bm, bn, bk = select_block_config(8, n, k, bits, block_n=128, block_k=block_k)
        assert n % bn or k % bk              # the case has a ragged edge
        out = qmm(x, pw, use_pallas=True, interpret=True, block_n=bn, block_k=block_k)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(_padded_route(x, pw, bm, bn, bk)))
        ref = qmm_ref(x, pw.packed, pw.scale, bits, k)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("orient", ["fwd", "adj"])
    @pytest.mark.parametrize("bits", BITS)
    def test_lofar_code_planes_are_never_copied(self, bits, orient):
        # LOFAR CS302: 870 baselines × a 256² sky, an 8-row batch
        m, n, k = {"fwd": (8, 870, 65536), "adj": (8, 65536, 870)}[orient]

        def call(x, packed, scale):
            return qmm(x, PackedWeights(packed, scale, bits, k), use_pallas=True)

        jaxpr = jax.make_jaxpr(call)(
            jax.ShapeDtypeStruct((m, k), jnp.float32),
            jax.ShapeDtypeStruct((n, packed_len(k, bits)), jnp.uint8),
            jax.ShapeDtypeStruct((1, n), jnp.float32))
        eqns = list(_jaxpr_eqns(jaxpr.jaxpr))
        assert any(e.primitive.name == "pallas_call" for e in eqns)
        padded = [e.invars[0].aval.dtype for e in eqns if e.primitive.name == "pad"]
        assert jnp.uint8 not in padded
        if orient == "adj":                  # x alone is padded, 870 → K step
            assert padded == [jnp.float32]


class TestQmmSemantics:
    @pytest.mark.parametrize("bits", BITS)
    @pytest.mark.slow
    def test_matches_dequantized_matmul(self, bits):
        """qmm == x @ Q(w)^T where Q is the framework quantizer (per-channel)."""
        key = jax.random.PRNGKey(2)
        x = jax.random.normal(key, (8, 100), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (32, 100), jnp.float32)
        kq = jax.random.fold_in(key, 2)
        pw = pack_weights(w, bits, kq)
        out = qmm(x, pw, use_pallas=False)
        w_deq = fake_quantize(w, bits, kq, channel_axis=0)
        ref = x @ w_deq.T
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)

    @pytest.mark.slow
    def test_8bit_quantization_error_small(self):
        key = jax.random.PRNGKey(3)
        x = jax.random.normal(key, (8, 128), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (32, 128), jnp.float32)
        pw = pack_weights(w, 8, jax.random.fold_in(key, 2))
        exact = x @ w.T
        out = qmm(x, pw, use_pallas=True, interpret=True)
        rel = float(jnp.linalg.norm(out - exact) / jnp.linalg.norm(exact))
        assert rel < 0.05

    def test_compression_bytes(self):
        w = jax.random.normal(jax.random.PRNGKey(4), (64, 512), jnp.float32)
        assert pack_weights(w, 2).nbytes == 64 * 128   # 16x vs f32
        assert pack_weights(w, 4).nbytes == 64 * 256   # 8x
        assert pack_weights(w, 8).nbytes == 64 * 512   # 4x


class TestPackedOperator:
    @pytest.mark.parametrize("bits", BITS)
    @pytest.mark.slow
    def test_complex_matvec_adjoint_consistency(self, bits):
        """<Φ̂x, r> == <x, Φ̂†r> exactly when fwd/adj share one deterministic
        quantization. (With stochastic keys the two orientations are
        *independent* quantizations by design — Algorithm 1's pairing — and the
        identity only holds in expectation.)"""
        key = jax.random.PRNGKey(5)
        phi = (
            jax.random.normal(key, (24, 48)) + 1j * jax.random.normal(jax.random.fold_in(key, 1), (24, 48))
        ).astype(jnp.complex64)
        op = pack_operator(phi, bits, key=None)
        x = jax.random.normal(jax.random.fold_in(key, 3), (48,), jnp.float32)
        r = (
            jax.random.normal(jax.random.fold_in(key, 4), (24,))
            + 1j * jax.random.normal(jax.random.fold_in(key, 5), (24,))
        ).astype(jnp.complex64)
        lhs = jnp.vdot(packed_matvec(op, x), r)
        rhs = jnp.vdot(x.astype(jnp.complex64), packed_rmatvec(op, r))
        denom = max(float(jnp.abs(lhs)), 1e-6)
        assert float(jnp.abs(lhs - rhs)) / denom < 1e-4

    @pytest.mark.slow
    def test_interpret_matches_ref_path(self):
        key = jax.random.PRNGKey(6)
        phi = (
            jax.random.normal(key, (30, 70)) + 1j * jax.random.normal(jax.random.fold_in(key, 1), (30, 70))
        ).astype(jnp.complex64)
        op = pack_operator(phi, 4, jax.random.fold_in(key, 2))
        x = jax.random.normal(jax.random.fold_in(key, 3), (70,), jnp.float32)
        a = packed_matvec(op, x, use_pallas=True, interpret=True)
        b = packed_matvec(op, x, use_pallas=False)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


class TestFusedDispatch:
    """The CPU fused path (``use_pallas=False``): every dispatch branch —
    matvec (m=1), batched minor, batched canonical (shared transposed codes),
    per-block — must match the unpack-everything oracle bit-for-bit up to f32
    accumulation order."""

    @pytest.mark.parametrize("gran", ["per_tensor", "per_channel"])
    @pytest.mark.parametrize("m", [1, 8])
    @pytest.mark.parametrize("bits", BITS)
    def test_fused_matches_ref(self, bits, m, gran):
        key = jax.random.PRNGKey(7)
        k, n = 129, 67  # deliberately unaligned: exercises padding in every branch
        x = jax.random.normal(key, (m, k), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (n, k), jnp.float32)
        pw = pack_weights(w, bits, jax.random.fold_in(key, 2), granularity=gran)
        ref = qmm_ref(x, pw.packed, pw.scale, bits, k)
        out = qmm(x, pw, use_pallas=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("m", [1, 8])
    @pytest.mark.parametrize("bits", BITS)
    def test_fused_per_block_matches_group_ref(self, bits, m):
        key = jax.random.PRNGKey(8)
        k, n, g = 160, 33, 32
        x = jax.random.normal(key, (m, k), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (n, k), jnp.float32)
        pw = pack_weights(w, bits, jax.random.fold_in(key, 2),
                          granularity=f"per_block:{g}")
        ref = qmm_group_ref(x, pw.packed, pw.scale, bits, k, g)
        out = qmm(x, pw, use_pallas=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("batch", [2, 8])
    @pytest.mark.parametrize("bits", BITS)
    def test_canonical_shared_path_matches_minor(self, bits, batch):
        """shared=True (gemm over the shared transposed codes — the solver's
        batched route) must equal shared=False (per-part minor dot) exactly:
        same codes, different contraction order."""
        key = jax.random.PRNGKey(9)
        phi = jax.random.normal(key, (48, 96), jnp.float32)
        op = pack_operator(phi, bits, jax.random.fold_in(key, 1), shared=True)
        xb = jax.random.normal(jax.random.fold_in(key, 2), (batch, 96), jnp.float32)
        rb = jax.random.normal(jax.random.fold_in(key, 3), (batch, 48), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(packed_matvec(op, xb, shared=True, use_pallas=False)),
            np.asarray(packed_matvec(op, xb, shared=False, use_pallas=False)),
            rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(packed_rmatvec(op, rb, shared=True, use_pallas=False)),
            np.asarray(packed_rmatvec(op, rb, shared=False, use_pallas=False)),
            rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("batch", [2, 8])
    def test_canonical_shared_path_complex(self, batch):
        key = jax.random.PRNGKey(10)
        # jaxlint: allow=JL001 -- test builds a c64 operator on purpose
        phi = (jax.random.normal(key, (24, 56))
               + 1j * jax.random.normal(jax.random.fold_in(key, 1), (24, 56))
               ).astype(jnp.complex64)
        op = pack_operator(phi, 8, jax.random.fold_in(key, 2), shared=True)
        xb = jax.random.normal(jax.random.fold_in(key, 3), (batch, 56), jnp.float32)
        # jaxlint: allow=JL001 -- test builds c64 observations on purpose
        rb = (jax.random.normal(jax.random.fold_in(key, 4), (batch, 24))
              + 1j * jax.random.normal(jax.random.fold_in(key, 5), (batch, 24))
              ).astype(jnp.complex64)
        np.testing.assert_allclose(
            np.asarray(packed_matvec(op, xb, shared=True, use_pallas=False)),
            np.asarray(packed_matvec(op, xb, shared=False, use_pallas=False)),
            rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(packed_rmatvec(op, rb, shared=True, use_pallas=False)),
            np.asarray(packed_rmatvec(op, rb, shared=False, use_pallas=False)),
            rtol=1e-5, atol=1e-5)


class TestSelectBlockConfig:
    def test_auto_clamps_to_small_problem(self):
        # fig5 smoke geometry: m=64, n=128, k=128 at 8 bits (k_unit=128)
        bm, bn, bk = select_block_config(64, 128, 128, 8)
        assert (bm, bn, bk) == (64, 128, 128)

    def test_auto_rounds_tiny_dims_to_hardware_minima(self):
        bm, bn, bk = select_block_config(3, 40, 100, 8)
        assert (bm, bn, bk) == (8, 128, 128)  # 8 sublanes, 128 lanes, 1 k-unit

    def test_auto_respects_group_size_lcm(self):
        # bits=2 → k_unit = 128·4 = 512; group 96 → lcm 1536 caps bk
        bm, bn, bk = select_block_config(128, 256, 4096, 2, group_size=96)
        assert bk % 1536 == 0

    def test_explicit_oversized_block_raises(self):
        with pytest.raises(ValueError, match="mostly padding"):
            select_block_config(4, 64, 256, 8, block_m=128)

    def test_explicit_misaligned_block_raises(self):
        with pytest.raises(ValueError, match="multiple of"):
            select_block_config(64, 256, 1024, 8, block_k=100)

    def test_explicit_aligned_block_accepted(self):
        assert select_block_config(128, 256, 1024, 8, block_m=64,
                                   block_n=128, block_k=512) == (64, 128, 512)

    @pytest.mark.parametrize("bits,group_size", [(2, None), (4, 16), (8, 64)])
    def test_auto_fits_vmem_budget(self, bits, group_size):
        # a 128-row batch: the group kernel's expanded x tile would need
        # ~14 MB at the default blocks (g = 16), so the K step shrinks to fit
        blocks = select_block_config(128, 65536, 65536, bits, group_size=group_size)
        assert vmem_bytes(*blocks, bits, group_size) <= VMEM_BUDGET_BYTES

    def test_explicit_block_over_vmem_budget_raises(self):
        with pytest.raises(ValueError, match="VMEM"):
            select_block_config(128, 1024, 65536, 4, group_size=16,
                                block_m=128, block_n=1024, block_k=4096)
