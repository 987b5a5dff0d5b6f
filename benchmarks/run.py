"""Benchmark harness entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit).
Speed is measured on the chip by ``bench/run.py``; these suites reproduce
the paper's tables and figures.

    PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="shorter training runs")
    ap.add_argument("--only", default=None, help="comma-separated suite names")
    args = ap.parse_args()
    steps = 150 if args.quick else 400

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (
        ablations,
        autoswitch_bench,
        layerwise,
        recipes,
        sparsity_sweep,
    )

    suites = {
        "autoswitch": lambda: autoswitch_bench.run(steps=max(300, steps)),  # Table 1
        "recipes": lambda: (recipes.table_mlp(steps=steps, seeds=(0,)),
                            recipes.table_lm(steps=120)),  # Tables 2-3
        "sparsity_sweep": lambda: sparsity_sweep.run(steps=120),            # Fig 5
        "layerwise": lambda: layerwise.run(steps=120),                      # Table 4
        "ablations": ablations.run,                                         # Figs 6-8
    }
    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    t0 = time.time()
    failed = []
    for name, fn in suites.items():
        if only and name not in only:
            continue
        print(f"# suite: {name}", flush=True)
        try:
            fn()
        except Exception as e:  # run the other suites, then fail
            print(f"{name}/FAILED,0.0,{type(e).__name__}:{e}", flush=True)
            failed.append(name)
    print(f"# total wall: {time.time()-t0:.1f}s")
    if failed:
        sys.exit(f"failed suites: {','.join(failed)}")


if __name__ == "__main__":
    main()
