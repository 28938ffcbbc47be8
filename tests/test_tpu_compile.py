"""Ahead-of-time compiles for one chip of a described TPU v5e, at the widths
the solver runs: the paper's LOFAR CS302 operator (870 baselines × a 256²
sky, c64 Φ, an 8-row batch).

Nothing here runs on a chip. The TPU compiler is installed without one and
refuses what the chip would refuse — a kernel over its VMEM budget, a block
not aligned to the (8, 128) tiling, a cast the TPU has no instruction for —
which interpret-mode tests cannot see. The topology is described inside a
module fixture (never at import: several test workers must collect the same
tests, and only one process may load the TPU library), and the persistent
compilation cache is off while these run: a compile for a described chip can
be written to the cache but not read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import qniht_batch
from repro.kernels.hsthresh.ops import hsthresh
from repro.kernels.qmm.kernel import (
    VMEM_BUDGET_BYTES,
    VMEM_LIMIT_BYTES,
    qmm_group_pallas,
    qmm_pallas,
    select_block_config,
    vmem_bytes,
)
from repro.kernels.sqround.ops import sqround
from repro.quant.formats import BY_BITS

M_BASELINES, N_PIXELS, BATCH = 870, 256 * 256, 8
HBM_BYTES = 16 * 10**9   # one v5e chip
# (M, N, K) of one qmm call: the forward Φ̂x and the adjoint Φ̂†r orientations
ORIENTATIONS = {"fwd": (BATCH, M_BASELINES, N_PIXELS),
                "adj": (BATCH, N_PIXELS, M_BASELINES)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        try:
            described = topologies.get_topology_desc(platform="tpu",
                                                     topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means: cannot describe
            jax.config.update("jax_enable_compilation_cache", cache_was_on)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield described
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _round_up(v, m):
    return -(-v // m) * m


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("orient", sorted(ORIENTATIONS))
@pytest.mark.parametrize("bits", [2, 4, 8])
def test_qmm_compiles_at_lofar_width(one_chip, bits, orient):
    m, n, k = ORIENTATIONS[orient]
    bm, bn, bk = select_block_config(m, n, k, bits)
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    vpb = BY_BITS[bits].values_per_byte
    _compile_kernel(
        lambda x, w, s: qmm_pallas(x, w, s, bits=bits, k_dim=kp, block_m=bm,
                                   block_n=bn, block_k=bk),
        _spec((mp, kp), jnp.float32, one_chip),
        _spec((np_, kp // vpb), jnp.uint8, one_chip),
        _spec((1, np_), jnp.float32, one_chip))


@pytest.mark.parametrize("orient", sorted(ORIENTATIONS))
@pytest.mark.parametrize("bits", [2, 4, 8])
def test_qmm_compiles_unpadded_at_lofar_width(one_chip, bits, orient):
    # the codes and scale row as stored, 870 wide: the grid's last block is
    # partial along N (forward) or along the packed K (adjoint)
    m, n, k = ORIENTATIONS[orient]
    bm, bn, bk = select_block_config(m, n, k, bits)
    mp, kp = _round_up(m, bm), _round_up(k, bk)
    vpb = BY_BITS[bits].values_per_byte
    _compile_kernel(
        lambda x, w, s: qmm_pallas(x, w, s, bits=bits, k_dim=k, block_m=bm,
                                   block_n=bn, block_k=bk),
        _spec((mp, kp), jnp.float32, one_chip),
        _spec((n, -(-k // vpb)), jnp.uint8, one_chip),
        _spec((1, n), jnp.float32, one_chip))


@pytest.mark.parametrize("orient", sorted(ORIENTATIONS))
@pytest.mark.parametrize("bits,group_size", [(2, 64), (4, 64), (8, 64), (4, 16)])
def test_qmm_group_compiles_at_lofar_width(one_chip, bits, group_size, orient):
    m, n, k = ORIENTATIONS[orient]
    bm, bn, bk = select_block_config(m, n, k, bits, group_size=group_size)
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    vpb = BY_BITS[bits].values_per_byte
    _compile_kernel(
        lambda x, w, s: qmm_group_pallas(x, w, s, bits=bits, k_dim=kp,
                                         group_size=group_size, block_m=bm,
                                         block_n=bn, block_k=bk),
        _spec((mp, kp), jnp.float32, one_chip),
        _spec((np_, kp // vpb), jnp.uint8, one_chip),
        _spec((np_, kp // group_size), jnp.float32, one_chip))


@pytest.mark.parametrize("orient", sorted(ORIENTATIONS))
@pytest.mark.parametrize("bits,group_size",
                         [(2, None), (4, None), (8, None), (2, 64), (4, 64), (8, 64), (4, 16)])
def test_lofar_tiles_fit_the_requested_vmem(bits, group_size, orient):
    m, n, k = ORIENTATIONS[orient]
    blocks = select_block_config(m, n, k, bits, group_size=group_size)
    assert vmem_bytes(*blocks, bits, group_size) <= VMEM_BUDGET_BYTES <= VMEM_LIMIT_BYTES
    if group_size is None:   # a grid step streams at least 512 KiB of codes
        _, bn, bk = blocks
        assert bn * bk // BY_BITS[bits].values_per_byte >= 512 * 1024


def test_adjoint_pads_baselines_to_896():
    # 870 baselines pad to 7 × 128, not to a multiple of a fixed 512-code step
    _, _, bk = select_block_config(*ORIENTATIONS["adj"], 8)
    assert _round_up(M_BASELINES, bk) == 896


def test_hsthresh_compiles_at_sky_width(one_chip):
    # vmapped over the batch rows, as the solver's H_s applies it
    _compile_kernel(jax.vmap(lambda v: hsthresh(v, 30, use_pallas=True)),
                    _spec((BATCH, N_PIXELS), jnp.float32, one_chip))


def test_sqround_compiles_at_operator_width(one_chip):
    _compile_kernel(lambda v, key: sqround(v, 2, key, use_pallas=True),
                    _spec((M_BASELINES, N_PIXELS), jnp.float32, one_chip),
                    _spec((2,), jnp.uint32, one_chip))


def test_packed_int2_solve_compiles_and_fits_one_chip(one_chip, monkeypatch):
    # the qmm dispatch asks the default backend, which is the CPU here: steer
    # it to the TPU branch so the solve is compiled as it runs on the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    solve = jax.jit(lambda phi, Y, key: qniht_batch(
        phi, Y, 30, 60, bits_phi=2, bits_y=8, key=key, requantize="fixed",
        backend="packed", real_signal=True, nonneg=True))
    compiled = solve.lower(
        _spec((M_BASELINES, N_PIXELS), jnp.complex64, one_chip),
        _spec((BATCH, M_BASELINES), jnp.complex64, one_chip),
        _spec((2,), jnp.uint32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used} B does not fit one chip's {HBM_BYTES} B"
