"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Default is CI-sized (``fast``);
``--full`` uses the paper-scale settings (256×256 sky, 100 realizations, ...).
``--json <path>`` additionally writes the rows as a JSON list of
``{name, us_per_call, derived}`` objects — the machine-readable perf
trajectory future PRs diff against.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback


def _parse_row(r: str) -> dict:
    name, us, derived = r.split(",", 2)
    return {"name": name, "us_per_call": float(us), "derived": derived}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sizes")
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset, e.g. --only fig1 fig11 roofline")
    ap.add_argument("--suite", action="append", default=None, metavar="NAME",
                    help="run one named suite (repeatable), e.g. "
                         "--suite mri-groupscale; combines with --only")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write all rows as JSON to PATH")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        fig1_sky,
        fig3_error_coeffs,
        fig4_methods,
        fig5_cpu_speedup,
        fig5_recovery_backend,
        fig6_bandwidth_model,
        fig7_rip_bits,
        fig9_clean,
        fig11_gaussian,
        fig_batch_scaling,
        fig_fault,
        fig_mri,
        kernels_micro,
        roofline,
    )

    class _FnSuite:
        """Adapter: expose a bare sweep function under the module protocol."""

        def __init__(self, fn):
            self.run = fn

    suites = {
        "fig1": fig1_sky,
        "fig3": fig3_error_coeffs,
        "fig4": fig4_methods,
        "fig5": fig5_cpu_speedup,
        "fig5b": fig5_recovery_backend,
        "fig6": fig6_bandwidth_model,
        "fig7": fig7_rip_bits,
        "fig9": fig9_clean,
        "fig11": fig11_gaussian,
        "mri": fig_mri,
        "mri-groupscale": _FnSuite(fig_mri.run_groupscale),
        "mri-fullimage": _FnSuite(fig_mri.run_fullimage),
        "batch-scaling": fig_batch_scaling,
        "fault": fig_fault,
        "kernels": kernels_micro,
        "roofline": roofline,
    }
    selected = list(args.only or []) + list(args.suite or [])
    if selected:
        unknown = [s for s in selected if s not in suites]
        if unknown:
            ap.error(f"unknown suite(s) {unknown}; choose from {sorted(suites)}")
        suites = {k: v for k, v in suites.items() if k in selected}
    else:
        # opt-in only: the full default run already covers these rows via "mri",
        # batch-scaling spawns forced-device-count subprocesses (minutes), and
        # fault measures checkpoint disk I/O that CI runners report noisily
        suites.pop("mri-groupscale")
        suites.pop("mri-fullimage")
        suites.pop("batch-scaling")
        suites.pop("fault")

    print("name,us_per_call,derived")
    failures = 0
    all_rows: list[str] = []
    for name, mod in suites.items():
        t0 = time.time()
        try:
            for r in mod.run(fast=not args.full):
                all_rows.append(r)
                print(r, flush=True)
        except Exception as e:
            failures += 1
            err_row = f"{name}/ERROR,0,{type(e).__name__}:{e}"
            all_rows.append(err_row)
            print(err_row, flush=True)
            traceback.print_exc(file=sys.stderr)
        print(f"# {name} took {time.time() - t0:.1f}s", flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump([_parse_row(r) for r in all_rows], f, indent=1)
        print(f"# wrote {len(all_rows)} rows to {args.json}", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
