"""Run the paper's recovery path once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4   # four chips: the sharded int2 batch only

Everything runs in this one process (a chip belongs to one process at a
time) and goes through the entry points a user calls — the functions behind
``python -m repro.launch.recover`` and ``python -m repro.launch.serve`` — at
the widths the paper uses; inputs come from fixed seeds.

(a) LOFAR CS302 at full width (``configs/lofar_cs302.py`` ``CONFIG``: 870
    baselines, a 256² sky, c64 Φ), an 8-row batch: Φ̂ packed at 2, 4 and 8
    bits through the Pallas qmm kernel with 8-bit observations, and the dense
    f32 solve of the same observations. Fails on a non-finite result, on a
    packed program without the Pallas call, or on quality below the bounds
    below.
(b) MRI at ``WAVELET`` size (256² Haar, per-band 8-bit k-space, 35 %
    variable-density sampling). Fails below ``MRI_PSNR_FLOOR_DB``.
(c) ``serve-continuous-packed`` through the ``ContinuousScheduler`` with
    every answer checked against its standalone solve.

``--chips 4`` runs only the (a) int2 problem with a 16-row batch, sharded by
rows over a 4-chip ``("batch",)`` mesh, against the one-chip solve in the
same process, and prints the per-item deviation.

Exits non-zero, printing no result, when JAX finds no TPU or a phase fails.
The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The compile cache follows ``repro.launch.compile_cache``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# Quality bounds for phase (a), on the batch mean of the relative error of the
# recovered sky: a packed solve may trail the dense f32 solve of the same
# observations by at most this much — twice the gap a CPU run of this phase at
# the same size showed (0.028, 0.082, 0.130 at 8, 4, 2 bits; see CHANGES.md).
LOFAR_REL_ERROR_MARGIN = {8: 0.06, 4: 0.17, 2: 0.26}
# Phase (a), per packed width: the Pallas operator's Φ̂x and Φ̂†r against the
# dense f32 products of the same quantized Φ̂ (identical codes, so only f32
# summation order separates them), as a relative l2 error.
OPERATOR_REL_TOL = 1e-4
# Phase (b): the bound tests/test_mri.py holds the wavelet recovery to.
MRI_PSNR_FLOOR_DB = 30.0


def _say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def _lofar_line(label: str, out: dict) -> None:
    _say(f"lofar {label}: rel_error_mean={out['rel_error_mean']} "
         f"rel_error_max={out['rel_error_max']} "
         f"support_recovery_mean={out['support_recovery_mean']} "
         f"support_recovery_min={out['support_recovery_min']}")
    _say(f"lofar {label}: compile_s={out['compile_s']}")
    _say(f"lofar {label}: solve_s={out['wall_s']}")


def _operator_agreement(phi, bits, key) -> tuple[float, float]:
    """Relative l2 error of the packed Φ̂x and Φ̂†r against the dense f32
    products of the same codes, on a random 8-row batch."""
    import jax
    import jax.numpy as jnp

    from repro.core.operators import DenseOperator, PackedStreamingOperator
    from repro.quant.quantize import fake_quantize

    packed = PackedStreamingOperator.pack(phi, bits, key)
    dense = DenseOperator(fake_quantize(phi, bits, key))
    kx, kr, ki = jax.random.split(jax.random.fold_in(key, bits), 3)
    x = jax.random.normal(kx, (8, phi.shape[1]), jnp.float32)
    r = jax.lax.complex(jax.random.normal(kr, (8, phi.shape[0]), jnp.float32),
                        jax.random.normal(ki, (8, phi.shape[0]), jnp.float32))

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    return rel(packed.mv(x), dense.mv(x)), rel(packed.rmv(r), dense.rmv(r))


def phase_lofar(key) -> None:
    from repro.configs.lofar_cs302 import CONFIG
    from repro.launch.recover import lofar_problem, recover_lofar

    phi = lofar_problem(CONFIG, key, 1)[0]
    for bits in (8, 4, 2):
        err_mv, err_rmv = _operator_agreement(phi, bits, key)
        _say(f"lofar int{bits} operator vs dense f32 of the same codes: "
             f"mv_rel_err={err_mv} rmv_rel_err={err_rmv}")
        _check(max(err_mv, err_rmv) <= OPERATOR_REL_TOL,
               f"lofar int{bits}: packed operator disagrees with its dense "
               f"products beyond {OPERATOR_REL_TOL}")
    del phi

    results = {}
    for bits in (None, 8, 4, 2):
        label = f"int{bits}" if bits else "f32"
        out = recover_lofar(CONFIG, "packed" if bits else "dense", bits,
                            CONFIG.bits_y, key, batch=8)
        _lofar_line(label, out)
        _check(out["finite"], f"lofar {label}: non-finite recovered sky")
        if bits:
            _check(out["pallas_kernel"],
                   f"lofar {label}: the packed program has no Pallas call "
                   "(tpu_custom_call)")
        results[bits] = out
    ref = results[None]["rel_error_mean"]
    for bits, margin in LOFAR_REL_ERROR_MARGIN.items():
        got = results[bits]["rel_error_mean"]
        _say(f"lofar int{bits}: rel_error_mean {got} vs f32 {ref} "
             f"(bound: f32 + {margin})")
        _check(got <= ref + margin,
               f"lofar int{bits}: rel_error_mean {got} exceeds f32 {ref} + {margin}")


def phase_mri(key) -> None:
    from repro.configs.mri_brain import WAVELET
    from repro.launch.recover import recover_mri

    out = recover_mri(WAVELET, WAVELET.bits_y, key,
                      granularity=WAVELET.scale_granularity)
    _say(f"mri {WAVELET.name}: psnr={out['psnr']} rel_error={out['rel_error']} "
         f"m={out['m']}")
    _say(f"mri {WAVELET.name}: wall_s={out['wall_s']} (compile included)")
    _check(math.isfinite(out["psnr"]) and out["psnr"] >= MRI_PSNR_FLOOR_DB,
           f"mri: psnr {out['psnr']} below {MRI_PSNR_FLOOR_DB} dB")


def phase_serve() -> None:
    from repro.configs.serve_batch import CONTINUOUS_PACKED
    from repro.launch.serve import serve_scheduled

    # raises unless every answer is bitwise its standalone solve
    out = serve_scheduled(CONTINUOUS_PACKED, "continuous", verify=True)
    _say(f"serve {CONTINUOUS_PACKED.name}: completed={out['completed']}/"
         f"{out['requests']} bitwise_vs_standalone={out['verify_bitwise']} "
         f"max_abs_dev={out['verify_max_abs_dev']}")
    _say(f"serve {CONTINUOUS_PACKED.name}: wall_s={out['wall_s']} "
         f"latency_p50_s={out['latency_p50_s']} "
         f"latency_p99_s={out['latency_p99_s']} (compiles included)")
    _check(out["completed"] == out["requests"],
           f"serve: {out['requests'] - out['completed']} requests not served")


def phase_sharded(key, n_chips: int) -> None:
    import numpy as np

    from repro.configs.lofar_cs302 import CONFIG
    from repro.launch.recover import (batch_solver, lofar_problem, solve_batch,
                                      solver_kwargs)

    phi, Y, _ = lofar_problem(CONFIG, key, 16)
    kw = solver_kwargs("packed", 2, CONFIG.bits_y, key, "fixed")
    solve = dict(real_signal=True, nonneg=True, **kw)
    one, c1, s1, _ = solve_batch(batch_solver(None, kw), phi, Y,
                                 CONFIG.n_sources, CONFIG.n_iters, **solve)
    _say(f"sharded int2: one chip compile_s={c1}")
    _say(f"sharded int2: one chip solve_s={s1}")
    many, _, s4, _ = solve_batch(batch_solver(n_chips, kw), phi, Y,
                                 CONFIG.n_sources, CONFIG.n_iters, **solve)
    _say(f"sharded int2: {n_chips} chips solve_s={s4} (compile included)")
    x1, x4 = np.asarray(one.x), np.asarray(many.x)
    _check(bool(np.isfinite(x1).all() and np.isfinite(x4).all()),
           "sharded int2: non-finite recovered sky")
    dev = np.max(np.abs(x1 - x4), axis=1)
    for b, d in enumerate(dev):
        _say(f"sharded int2: item {b} max_abs_dev={float(d)} "
             f"bitwise={bool(np.array_equal(x1[b], x4[b]))}")
    _say(f"sharded int2: per-item bit-identity across mesh widths holds: "
         f"{bool(np.array_equal(x1, x4))}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded int2 batch on a 4-chip mesh "
                         "against one chip")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)}")
    _say(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
         f"compile cache {enable_compile_cache()}")

    key = jax.random.PRNGKey(0)  # the CLIs' default --seed
    if args.chips == 4:
        phase_sharded(key, args.chips)
    else:
        phase_lofar(key)
        phase_mri(key)
        phase_serve()
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
