"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only mso,mc,...]

Prints ``name,us_per_call,derived`` CSV rows and saves artifacts/*.json.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

import jax

from repro.launch.runtime import enable_compile_cache

MODULES = ["stepcost", "scan_parallel", "mso", "memory_capacity",
           "mc_connectivity", "roofline", "serve_engine", "loadgen",
           "params_api"]
# The paper reproductions check float64 claims; the serving modules
# (roofline, serve_engine, loadgen) run the device path in float32.
F64_MODULES = {"stepcost", "scan_parallel", "mso", "memory_capacity",
               "mc_connectivity", "params_api"}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced grids (CI-speed)")
    ap.add_argument("--only", default=None,
                    help="comma-separated module subset")
    args = ap.parse_args()
    mods = args.only.split(",") if args.only else MODULES

    print("name,us_per_call,derived")
    failures = 0
    for name in mods:
        jax.config.update("jax_enable_x64", name in F64_MODULES)
        mod = __import__(f"benchmarks.{name}", fromlist=["main"])
        t0 = time.time()
        try:
            rows = mod.main(quick=args.quick)
            for r in rows:
                print(r, flush=True)
            print(f"bench.{name}.wall_s,{(time.time() - t0) * 1e6:.0f},"
                  f"ok", flush=True)
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"bench.{name}.wall_s,{(time.time() - t0) * 1e6:.0f},"
                  f"FAILED", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
