"""Pallas TPU kernels for the compute hot-spots the paper optimizes.

Each kernel ships as <name>/kernel.py (pl.pallas_call + BlockSpec VMEM tiling),
<name>/ops.py (jit'd public wrapper with padding + backend dispatch) and
<name>/ref.py (pure-jnp oracle used by the tests' assert_allclose sweeps):

* ``qmm``       — packed int2/4/8 dequant matmul (the paper's AVX2/FPGA engines)
* ``sqround``   — stochastic rounding quantizer (paper §9's XORShift path)
* ``hsthresh``  — streaming hard-threshold H_s (paper §8's FPGA top-S search)
* ``flashattn`` — fused online-softmax attention (32k-prefill substrate)

Backends: on a TPU the ops.py wrappers run the Pallas kernels (compiled by
Mosaic; ``tests/test_tpu_compile.py`` compiles them for a described v5e at
deployment widths). Elsewhere they dispatch to jnp paths (``qmm``'s fused
pipeline, the ref.py oracles), and ``interpret=True`` executes the kernel
bodies on CPU for correctness tests.
"""
from repro.kernels.flashattn.ops import flash_attention
from repro.kernels.hsthresh.ops import hsthresh
from repro.kernels.qmm.ops import (
    PackedOperator,
    PackedWeights,
    pack_operator,
    pack_weights,
    packed_matvec,
    packed_rmatvec,
    qmm,
)
from repro.kernels.sqround.ops import sqround

__all__ = [
    "flash_attention",
    "hsthresh",
    "PackedOperator",
    "PackedWeights",
    "pack_operator",
    "pack_weights",
    "packed_matvec",
    "packed_rmatvec",
    "qmm",
    "sqround",
]
