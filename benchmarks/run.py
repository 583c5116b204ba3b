"""Benchmark harness — one module per paper table/figure + framework extras.
Prints ``name,us_per_call,derived`` CSV (harness contract).

    PYTHONPATH=src python -m benchmarks.run [--only substring]

A suite that raises still lets the others run, prints an ``<suite>/ERROR``
row, and makes the run exit 1.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None)
    args = ap.parse_args()

    from repro.runtime import compile_cache
    compile_cache.enable()

    from benchmarks import (bench_archs, bench_data_consistency,
                            bench_distributed, bench_kernels,
                            bench_projectors, bench_recon, bench_serve)
    suites = {
        "table1_projectors": bench_projectors.run,
        "recon_pipeline": bench_recon.run,
        "fig3_data_consistency": bench_data_consistency.run,
        "kernels": bench_kernels.run,
        "archs": bench_archs.run,
        "serve": bench_serve.run,
        "distributed": bench_distributed.run,
    }
    print("name,us_per_call,derived", flush=True)
    failed = []
    for name, fn in suites.items():
        if args.only and args.only not in name:
            continue
        rows: list = []
        try:
            fn(rows)
        except Exception:  # noqa: BLE001 — report and continue
            traceback.print_exc()
            rows.append((f"{name}/ERROR", -1.0, "failed"))
            failed.append(name)
        for rname, us, derived in rows:
            # .6g, not .1f: quality rows carry metric values (SSIM ~0.9,
            # residuals ~0.01) that a fixed single decimal would destroy.
            print(f"{rname},{us:.6g},{derived}", flush=True)
        # drop compiled programs between suites (CPU-RAM hygiene)
        import jax
        jax.clear_caches()
    if failed:
        print(f"failed suites: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
