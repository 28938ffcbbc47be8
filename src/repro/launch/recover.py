"""CS recovery driver — the paper's own end-to-end pipeline as a launcher.

``python -m repro.launch.recover --config lofar --bits-phi 2 --bits-y 8``
simulates the station, builds Φ, quantizes per Algorithm 1 and recovers the
sky, reporting the Fig. 1/4 metrics.

Backends (``--backend``):

* ``dense``  — full-precision NIHT: Φ stays f32/c64, the Theorem 2 baseline.
* ``fake``   — QNIHT with *fake* quantization: Φ̂'s values are quantized
  (``--requantize pair`` redraws the stochastic pair each iteration —
  Algorithm 1 verbatim; ``fixed`` quantizes once) but carried as dense floats.
  Faithful to the paper's math; streams full-precision bytes.
* ``packed`` — QNIHT streaming *packed* uint8 codes through the Pallas qmm
  kernels (forces ``requantize=fixed``: the deployed systems stream
  pre-quantized data). Same iterates as ``fake --requantize fixed`` up to f32
  accumulation, with 32/bits× fewer operator bytes per matvec — the paper's
  Fig. 5/6 speed-up mode.

``--batch B`` recovers B observations of the same Φ̂ at once (``qniht_batch``):
one packed Φ̂ stream serves the whole batch per iteration. Adding
``--devices N`` splits those rows over an N-device ``("batch",)`` mesh
(``qniht_batch_sharded`` — the same computation per item, with per-shard early exit;
on CPU the driver forces N host devices for you). The multi-chunk streaming
loop lives in ``python -m repro.launch.serve``.

``--scale-granularity`` picks the quantizer scale layout (default
``per_tensor``, the paper's single c): with ``--backend packed`` it selects the
packed Φ̂ scale granularity (``per_channel``, or ``per_block`` with
``--group-size G``); on the MRI configs it selects the *observation* quantizer
(``per_band`` radial k-space scaling, ``--group-size`` = number of bands) —
the mechanism that keeps ``--bits-y 4`` and below usable against k-space's
dynamic range.

``--config mri`` (also ``mri-bench``/``mri-smoke``) runs the paper's §5 MRI
workload: an s-sparse brain phantom recovered from quantized
variable-density-subsampled k-space. Φ is the *matrix-free*
``SubsampledFourierOperator`` (implicit 2D FFT + mask) — no dense Φ ever
exists, which is what makes the 256×256 config representable at all — so the
backend knobs don't apply; ``--bits-y`` is the precision under study and the
driver reports PSNR in image space alongside relative error. With
``--batch B``, B randomized brain phantoms share one sampling mask and are
recovered in a single ``qniht_batch`` call.

``--sparsity-basis`` picks the MRI recovery model: ``pixel`` (the s-sparse
phantom of the exact-sparsity guarantees) or ``haar``/``db4`` — the **full,
unsparsified** phantom recovered through the composed Φ = P_Ω F W†
(``ComposedOperator`` of the Fourier factor with a wavelet synthesis; still
matrix-free end to end). ``--config mri-wavelet`` (also ``-bench``/``-smoke``)
preselects the haar basis with wavelet-sized s and per-band scaling.
"""
from __future__ import annotations

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.gaussian_toy import CONFIG as GAUSS_CONFIG, SMOKE as GAUSS_SMOKE
from repro.configs.lofar_cs302 import BENCH as LOFAR_BENCH, CONFIG as LOFAR_CONFIG, SMOKE as LOFAR_SMOKE
from repro.configs.mri_brain import (
    BENCH as MRI_BENCH,
    CONFIG as MRI_CONFIG,
    SMOKE as MRI_SMOKE,
    WAVELET as MRI_WAVELET,
    WAVELET_BENCH as MRI_WAVELET_BENCH,
    WAVELET_SMOKE as MRI_WAVELET_SMOKE,
)
from repro.core import (
    niht,
    psnr,
    qniht,
    qniht_batch,
    qniht_batch_sharded,
    relative_error,
    source_recovery,
    support_recovery,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.sensing import (
    Station,
    brain_phantom,
    make_gaussian_problem,
    make_mri_problem,
    make_sky,
    measurement_matrix,
    mri_observations,
    quantize_observations,
    sparsify_image,
    visibilities,
)


def batch_solver(devices, kw, ckpt=None):
    """qniht_batch, or its mesh-sharded twin when ``--devices`` asks for one
    (the same computation per item — see repro.parallel.batch). ``early_exit`` is on
    whenever the per-iteration operators are stationary (it is invalid under
    requantize='pair', which redraws Φ̂ each iteration).

    ``ckpt`` (``--checkpoint-dir``): route the solve through the segmented
    checkpointed driver (:func:`repro.launch.resilience.recover_resilient`) —
    same arguments, bit-identical result, preemption-safe."""
    early = not (kw.get("bits_phi") and kw.get("requantize", "pair") == "pair")
    if ckpt:
        from repro.launch.resilience import recover_resilient

        def run(phi, Y, s, n_iters, **kws):
            if devices:
                kws.setdefault("early_exit", early)
            return recover_resilient(phi, Y, s, n_iters,
                                     n_devices=devices or None, verbose=True,
                                     **ckpt, **kws)
        return run
    if devices:
        return partial(qniht_batch_sharded, n_devices=devices, early_exit=early)
    return qniht_batch


def solve_batch(solver, phi, Y, s, n_iters, **kw):
    """Run a batched solve with its compilation timed apart from the solve.

    Returns ``(result, compile_s, solve_s, compiled)``. A jitted solver
    (``qniht_batch``) is compiled ahead of time, so ``compile_s`` is the
    compile alone — a persistent-cache hit shows as a short one — and
    ``compiled`` is the executable (its ``as_text()`` names the kernels it
    calls). Other solvers (sharded, checkpointed) compile inside their first
    call: ``compile_s`` and ``compiled`` are then ``None`` and ``solve_s``
    includes the compile."""
    if not hasattr(solver, "lower"):
        t0 = time.perf_counter()
        res = solver(phi, Y, s, n_iters, **kw)
        jax.block_until_ready(res.x)
        return res, None, time.perf_counter() - t0, None
    key = kw.pop("key", None)
    t0 = time.perf_counter()
    compiled = solver.lower(phi, Y, s, n_iters, key=key, **kw).compile()
    t1 = time.perf_counter()
    res = compiled(phi, Y, key=key)
    jax.block_until_ready(res.x)
    return res, t1 - t0, time.perf_counter() - t1, compiled


def _batch_metrics(X_true, res, compile_s, solve_s, compiled):
    """Per-batch summary shared by the batched recover paths."""
    batch = X_true.shape[0]
    rel = [float(relative_error(res.x[b], X_true[b])) for b in range(batch)]
    out = {"batch": batch, "rel_error_mean": sum(rel) / batch,
           "rel_error_max": max(rel),
           "finite": bool(jnp.all(jnp.isfinite(res.x)))}
    if compiled is not None:
        out["compile_s"] = compile_s
        out["pallas_kernel"] = "tpu_custom_call" in compiled.as_text()
    out["wall_s"] = solve_s
    return out


def _single_via_ckpt(ckpt, phi, y, s, n_iters, **kw):
    """One-problem solve through the segmented checkpointed driver (wraps the
    observation as a 1-row batch, exactly what ``qniht`` itself does)."""
    from repro.launch.resilience import recover_resilient

    res = recover_resilient(phi, y[None, :], s, n_iters, verbose=True,
                            **ckpt, **kw)
    return type(res)(x=res.x[0],
                     trace=jax.tree_util.tree_map(lambda t: t[:, 0], res.trace))


def solver_kwargs(backend, bits_phi, bits_y, key, requantize,
                  granularity="per_tensor", group_size=None):
    if granularity != "per_tensor" and backend != "packed":
        raise ValueError(
            f"--scale-granularity {granularity} scales the packed Φ̂ stream; "
            f"combine it with --backend packed (got --backend {backend})")
    if backend == "dense":
        return dict()
    kw = dict(
        bits_phi=bits_phi,
        bits_y=bits_y,
        key=key,
        requantize="fixed" if backend == "packed" else requantize,
        backend="packed" if backend == "packed" else "dense",
    )
    if granularity != "per_tensor":
        kw.update(scale_granularity=granularity, group_size=group_size)
    return kw


def lofar_problem(cs, key, batch):
    """The station's Φ and ``batch`` random skies with their visibilities:
    ``(phi, Y, X_true)``, deterministic in ``key`` (row b folds in b)."""
    st = Station(n_antennas=cs.n_antennas, seed=cs.seed)
    phi = measurement_matrix(st, cs.resolution, cs.extent)
    skies = [make_sky(cs.resolution, cs.n_sources, jax.random.fold_in(key, b),
                      min_sep=cs.min_sep) for b in range(batch)]
    Y = jnp.stack([visibilities(phi, x, cs.snr_db, jax.random.fold_in(key, b))[0]
                   for b, x in enumerate(skies)])
    return phi, Y, jnp.stack(skies)


def recover_lofar(cs, backend, bits_phi, bits_y, key, requantize="pair", batch=0,
                  granularity="per_tensor", group_size=None, devices=None,
                  ckpt=None):
    kw = solver_kwargs(backend, bits_phi, bits_y, key, requantize,
                       granularity, group_size)
    if batch:
        phi, Y, X_true = lofar_problem(cs, key, batch)
        res, compile_s, solve_s, compiled = solve_batch(
            batch_solver(devices, kw, ckpt), phi, Y, cs.n_sources, cs.n_iters,
            real_signal=True, nonneg=True, **kw)
        sup = [float(support_recovery(res.x[b], X_true[b], cs.n_sources))
               for b in range(batch)]
        return {**_batch_metrics(X_true, res, compile_s, solve_s, compiled),
                "support_recovery_mean": sum(sup) / batch,
                "support_recovery_min": min(sup)}
    st = Station(n_antennas=cs.n_antennas, seed=cs.seed)
    phi = measurement_matrix(st, cs.resolution, cs.extent)
    x = make_sky(cs.resolution, cs.n_sources, key, min_sep=cs.min_sep)
    y, _ = visibilities(phi, x, cs.snr_db, key)
    t0 = time.time()
    if ckpt:
        res = _single_via_ckpt(ckpt, phi, y, cs.n_sources, cs.n_iters,
                               real_signal=True, nonneg=True, **kw)
    elif backend == "dense":
        res = niht(phi, y, cs.n_sources, cs.n_iters, real_signal=True, nonneg=True)
    else:
        res = qniht(phi, y, cs.n_sources, cs.n_iters, real_signal=True,
                    nonneg=True, **kw)
    jax.block_until_ready(res.x)
    wall = time.time() - t0
    r = cs.resolution
    return {
        "rel_error": float(relative_error(res.x, x)),
        "support_recovery": float(support_recovery(res.x, x, cs.n_sources)),
        "source_recovery": float(source_recovery(
            jnp.real(res.x).reshape(r, r), x.reshape(r, r), cs.n_sources, 1)),
        "wall_s": wall,
        "resid_true": [float(v) for v in res.trace.resid_true[-3:]],
    }


def recover_gaussian(g, backend, bits_phi, bits_y, key, requantize="pair", batch=0,
                     granularity="per_tensor", group_size=None, devices=None,
                     ckpt=None):
    prob = make_gaussian_problem(g.m, g.n, g.s, 20.0, key)
    kw = solver_kwargs(backend, bits_phi, bits_y, key, requantize,
                       granularity, group_size)
    if batch:
        # B problems sharing phi: fresh sparse signals + noise per row.
        probs = [make_gaussian_problem(g.m, g.n, g.s, 20.0,
                                       jax.random.fold_in(key, b + 1),
                                       phi=prob.phi) for b in range(batch)]
        Y = jnp.stack([p.y for p in probs])
        X_true = jnp.stack([p.x_true for p in probs])
        return _batch_metrics(X_true, *solve_batch(
            batch_solver(devices, kw, ckpt), prob.phi, Y, g.s, g.n_iters, **kw))
    if ckpt:
        res = _single_via_ckpt(ckpt, prob.phi, prob.y, g.s, g.n_iters, **kw)
    else:
        res = (niht(prob.phi, prob.y, g.s, g.n_iters) if backend == "dense" else
               qniht(prob.phi, prob.y, g.s, g.n_iters, **kw))
    return {"rel_error": float(relative_error(res.x, prob.x_true)),
            "support_recovery": float(support_recovery(res.x, prob.x_true, g.s))}


def recover_mri(cfg, bits_y, key, batch=0, granularity="per_tensor", n_bands=None,
                sparsity_basis=None, devices=None, ckpt=None):
    """Matrix-free §5 workload: image-space PSNR/relative error of the
    recovered phantom. ``bits_y=None`` → full-precision observations (the
    32-bit baseline); ``batch`` recovers B randomized brain phantoms sharing
    one sampling mask in a single batched call. ``granularity="per_band"``
    quantizes the observations with one scale per radial k-space band
    (``n_bands`` of them) instead of the paper's single c_y.
    ``sparsity_basis`` (default: the config's) selects pixel sparsity or the
    composed wavelet model Φ = P_Ω F W† over the full phantom."""
    basis = sparsity_basis if sparsity_basis is not None else cfg.sparsity_basis
    prob = make_mri_problem(cfg.resolution, cfg.n_sparse, cfg.fraction, key,
                            density=cfg.density, center_fraction=cfg.center_fraction,
                            snr_db=cfg.snr_db, phantom=cfg.phantom,
                            sparsity_basis=basis,
                            wavelet_levels=cfg.wavelet_levels)
    r = cfg.resolution
    n_bands = n_bands if n_bands is not None else cfg.n_bands
    # wavelet coefficients are signed; only the pixel basis is a nonneg image
    kw = dict(real_signal=True, nonneg=basis == "pixel")

    def prep(y):
        """Quantize observations per granularity; per-band happens up front
        (qniht's own bits_y path is the per-tensor draw)."""
        if not bits_y:
            return y
        if granularity == "per_band":
            return quantize_observations(y, bits_y, key, granularity="per_band",
                                         op=prob.op, n_bands=n_bands)
        kw.update(bits_y=bits_y, key=key)
        return y

    if batch:
        if basis == "pixel":
            # per-row jitter breaks the phantom skull ring's exact-1.0 top-k
            # ties so the B rows are genuinely distinct problems
            def truth(b):
                img = brain_phantom(r, jax.random.fold_in(key, b))
                jitter = 1e-3 * jax.random.uniform(
                    jax.random.fold_in(key, 100 + b), img.shape)
                return sparsify_image(img + jitter, cfg.n_sparse)
        else:
            # full phantoms: rows differ by construction, no thresholding ties
            def truth(b):
                return brain_phantom(r, jax.random.fold_in(key, b)).ravel()

        Img_true = jnp.stack([truth(b) for b in range(batch)])
        Y, _ = mri_observations(getattr(prob.op, "kspace_op", prob.op), Img_true,
                                cfg.snr_db, jax.random.fold_in(key, batch))
        Y = prep(Y)
        res, _, wall, _ = solve_batch(batch_solver(devices, kw, ckpt), prob.op, Y,
                                      cfg.n_sparse, cfg.n_iters, **kw)
        Img_hat = prob.to_image(res.x)
        ps = [float(psnr(Img_hat[b].reshape(r, r), Img_true[b].reshape(r, r)))
              for b in range(batch)]
        rel = [float(relative_error(Img_hat[b], Img_true[b])) for b in range(batch)]
        return {"basis": basis, "batch": batch, "m": prob.op.shape[0],
                "psnr_mean": sum(ps) / batch, "psnr_min": min(ps),
                "rel_error_mean": sum(rel) / batch,
                "rel_error_max": max(rel), "wall_s": wall}
    y = prep(prob.y)
    t0 = time.time()
    if ckpt:
        res = _single_via_ckpt(ckpt, prob.op, y, cfg.n_sparse, cfg.n_iters, **kw)
    else:
        res = qniht(prob.op, y, cfg.n_sparse, cfg.n_iters, **kw)
    jax.block_until_ready(res.x)
    wall = time.time() - t0
    img_hat = prob.to_image(res.x)
    out = {
        "basis": basis,
        "m": prob.op.shape[0],
        "psnr": float(psnr(img_hat.reshape(r, r), prob.image_true.reshape(r, r))),
        "rel_error": float(relative_error(img_hat, prob.image_true)),
        "wall_s": wall,
        "phi_nbytes": prob.op.nbytes,
    }
    if bits_y and granularity == "per_band":
        out["y_scale_bytes"] = 4 * n_bands
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--config", default="lofar-bench",
                    choices=["lofar", "lofar-bench", "lofar-smoke",
                             "gaussian", "gaussian-smoke",
                             "mri", "mri-bench", "mri-smoke",
                             "mri-wavelet", "mri-wavelet-bench",
                             "mri-wavelet-smoke"])
    ap.add_argument("--backend", default="fake", choices=["dense", "fake", "packed"],
                    help="dense: f32 NIHT baseline; fake: quantized values, dense "
                         "compute (Algorithm 1); packed: stream packed codes via "
                         "the Pallas qmm kernels (forces --requantize fixed)")
    ap.add_argument("--bits-phi", type=int, default=2)
    ap.add_argument("--bits-y", type=int, default=8)
    ap.add_argument("--full-precision", action="store_true",
                    help="alias for --backend dense")
    ap.add_argument("--requantize", default="pair", choices=["pair", "fixed"])
    ap.add_argument("--batch", type=int, default=0,
                    help="recover B observations of one Φ̂ at once (qniht_batch)")
    ap.add_argument("--devices", type=int, default=None,
                    help="shard the --batch rows over an N-device ('batch',) "
                         "mesh (qniht_batch_sharded; the same computation per item). "
                         "On CPU this also forces N host devices when jax has "
                         "not initialized yet")
    ap.add_argument("--scale-granularity", default=None,
                    choices=["per_tensor", "per_channel", "per_block", "per_band"],
                    help="quantizer scale layout: per_channel/per_block apply to "
                         "the packed Φ̂ stream (--backend packed), per_band to "
                         "the MRI observation quantizer (default: the MRI "
                         "config's scale_granularity, else per_tensor)")
    ap.add_argument("--group-size", type=int, default=None,
                    help="per_block group size along the contraction axis, or "
                         "the number of radial k-space bands for per_band "
                         "(default: the MRI config's n_bands)")
    ap.add_argument("--sparsity-basis", default=None,
                    choices=["pixel", "haar", "db4"],
                    help="MRI recovery model: pixel sparsity (exact s-sparse "
                         "phantom) or a wavelet basis — the full unsparsified "
                         "phantom via the composed Φ = P_Ω F W† "
                         "(default: the config's sparsity_basis)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint-dir", default=None,
                    help="run the solve in checkpointed segments persisted to "
                         "this directory (preemption-safe: SIGTERM/SIGINT "
                         "writes a final checkpoint and exits cleanly; the "
                         "result is bit-identical to an unsegmented run)")
    ap.add_argument("--ckpt-every", type=int, default=10,
                    help="iterations per segment/checkpoint (with --checkpoint-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest complete checkpoint in "
                         "--checkpoint-dir; works across --devices widths "
                         "(elastic) and falls back to a fresh start when the "
                         "directory has no restorable checkpoint")
    ap.add_argument("--sanitize", action="store_true",
                    help="run the solve under repro.analysis.sanitize: any "
                         "NaN/Inf raises at the producing op, and a "
                         "[sanitize] line reports backend compile counts")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a JAX profiler trace of the whole solve "
                         "under this directory (TensorBoard/Perfetto format; "
                         "see docs/performance.md)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if (args.resume or args.ckpt_every != 10) and not args.checkpoint_dir:
        ap.error("--resume/--ckpt-every need --checkpoint-dir")
    if args.ckpt_every < 1:
        ap.error("--ckpt-every must be >= 1")
    ckpt = (dict(checkpoint_dir=args.checkpoint_dir, ckpt_every=args.ckpt_every,
                 resume=args.resume) if args.checkpoint_dir else None)

    if args.devices and not args.batch:
        ap.error("--devices shards the batch axis; combine it with --batch B")
    if args.devices:
        # only effective before the first jax call of this process
        from repro.parallel.batch import force_host_devices

        force_host_devices(args.devices)
    backend = "dense" if args.full_precision else args.backend
    key = jax.random.PRNGKey(args.seed)
    # None = unset: non-MRI configs fall back to per_tensor, MRI configs to
    # their own scale_granularity. An EXPLICIT --scale-granularity always wins
    # (the wavelet configs default to per_band, and the per-tensor baseline
    # must stay reachable against them).
    gran = args.scale_granularity or "per_tensor"
    if args.sparsity_basis and not args.config.startswith("mri"):
        ap.error("--sparsity-basis selects the MRI recovery model; use an mri config")
    from repro.launch.resilience import Preempted

    import contextlib

    if args.sanitize:
        from repro.analysis.sanitize import sanitize as sanitize_ctx

        ctx = sanitize_ctx()
    else:
        ctx = contextlib.nullcontext()
    prof = (jax.profiler.trace(args.profile_dir) if args.profile_dir
            else contextlib.nullcontext())
    try:
        with prof, ctx as counter:
            if args.config.startswith("lofar"):
                if gran == "per_band":
                    ap.error("per_band is the MRI observation granularity; use an mri config")
                cs = {"lofar": LOFAR_CONFIG, "lofar-bench": LOFAR_BENCH,
                      "lofar-smoke": LOFAR_SMOKE}[args.config]
                out = recover_lofar(cs, backend, args.bits_phi, args.bits_y, key,
                                    args.requantize, args.batch, gran, args.group_size,
                                    devices=args.devices, ckpt=ckpt)
                label = ("32bit" if backend == "dense"
                         else f"{args.bits_phi}&{args.bits_y}bit[{backend}]")
            elif args.config.startswith("mri"):
                if gran in ("per_channel", "per_block"):
                    ap.error("the MRI Φ is matrix-free (nothing packed to scale); "
                             "use --scale-granularity per_band for the observations")
                cs = {"mri": MRI_CONFIG, "mri-bench": MRI_BENCH,
                      "mri-smoke": MRI_SMOKE, "mri-wavelet": MRI_WAVELET,
                      "mri-wavelet-bench": MRI_WAVELET_BENCH,
                      "mri-wavelet-smoke": MRI_WAVELET_SMOKE}[args.config]
                bits_y = None if backend == "dense" else args.bits_y
                gran = args.scale_granularity or cs.scale_granularity
                out = recover_mri(cs, bits_y, key, args.batch, gran, args.group_size,
                                  sparsity_basis=args.sparsity_basis,
                                  devices=args.devices, ckpt=ckpt)
                basis = args.sparsity_basis or cs.sparsity_basis
                label = ("32bit[matrix-free]" if bits_y is None
                         else f"y@{bits_y}bit[{gran},matrix-free]") + f"[{basis}]"
            else:
                if gran == "per_band":
                    ap.error("per_band is the MRI observation granularity; use an mri config")
                g = GAUSS_CONFIG if args.config == "gaussian" else GAUSS_SMOKE
                out = recover_gaussian(g, backend, args.bits_phi, args.bits_y, key,
                                       args.requantize, args.batch, gran, args.group_size,
                                       devices=args.devices, ckpt=ckpt)
                label = ("32bit" if backend == "dense"
                         else f"{args.bits_phi}&{args.bits_y}bit[{backend}]")
    except Preempted as e:
        print(f"[recover] {e}; restart with --resume to continue", flush=True)
        return
    if counter is not None:
        print(f"[sanitize] ok {counter.summary()} debug_nans=on "
              "debug_infs=on", flush=True)
    print(f"[recover] {args.config} {label}: " +
          " ".join(f"{k}={v if not isinstance(v, float) else round(v, 4)}"
                   for k, v in out.items()))


if __name__ == "__main__":
    main()
