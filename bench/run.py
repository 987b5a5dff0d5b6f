"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; the mix's ``kind`` picks the runner (``train``).  The run
loads, warms up every shape it will use, measures for ``--seconds``,
checks what the timed path produced against the plain reference, and
prints one JSON object last on standard output.  Without the TPUs the
cell asks for it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

CACHE_DIR = BENCH / ".jax_cache"


def enable_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    for every program however small, so only a cell's first run compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the system under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2
    from bench.harness import cells, device, spec

    cell = spec.load_cell(args.workload)
    enable_cache()
    try:
        devs = device.require(cell.chips)
    except device.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result = cells.run(cell, devs, device.peaks(devs[0].device_kind), args.seed,
                       args.seconds, bool(args.trace), T_START)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
