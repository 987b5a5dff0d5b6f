"""The train step's device time by named scope, for one traced run.

    python3 bench/tools/scopes.py [DIR]

``DIR`` is a traced run's profile directory (default ``bench/.traces``,
where the last traced run left it).  The known scopes are the program's
and those that the metric files ``BENCHMARK.json`` names declare, each
nested in its region.  Prints each declared scope with its region, then,
per step in ms: each scope by pass (forward, backward, remat recompute),
each region's total (a declared scope is a region of its own) and its
longest operations, the longest operations by name split by scope, the ten
longest idle gaps named by the program span open at their middle
(``train.step`` or ``idle``), the median host time of a ``train.step``
call, and the busy time beside ``trace.reduce``'s reading of the same
run's ``.xplane.pb``.
"""
from __future__ import annotations

import glob
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main() -> int:
    from bench.harness import cells, scopes, spec, trace

    directory = sys.argv[1] if len(sys.argv) > 1 else str(cells.TRACE_DIR)
    nested = scopes.declared(spec.metric_reader(m["name"])
                             for m in spec.load_benchmark()["per_layer"])
    p = scopes.load(directory, nested)
    if p is None or not p.steps:
        print(f"no device operations or {scopes.STEP_SPAN} spans under "
              f"{directory}", file=sys.stderr)
        return 1
    for name, parent in nested.items():
        print(f"scope {name} nested in {parent}")
    per = lambda ns: p.per_step_ms(ns)
    print(f"steps {p.steps}  devices {p.devices}  window {p.window_ns / 1e6:.3f} ms"
          f"  busy {p.busy_ns / 1e6:.3f} ms ({per(p.busy_ns * p.devices):.3f} per step)"
          f"  dispatch median {p.dispatch_ms():.3f} ms")
    xplanes = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                        recursive=True)
    if xplanes:
        s = trace.reduce(xplanes[0], p.devices)
        print(f"xplane busy {s.busy_s * 1e3:.3f} ms "
              f"(export / xplane {p.busy_ns / 1e9 / s.busy_s:.5f})")
    print(f"\n{'scope':<12}" + "".join(f"{x:>12}" for x in scopes.PASSES)
          + f"{'total':>12}")
    for scope, row in p.table().items():
        print(f"{scope:<12}" + "".join(f"{row[x]:>12.3f}" for x in scopes.PASSES)
              + f"{sum(row.values()):>12.3f}")
    print("\nregion (ms per step, share of busy), longest operations")
    busy = per(p.busy_ns * p.devices)
    for region in p.regions:
        ms = p.region_ms(region)
        tops = ", ".join(f"{n} {t:.3f}" for n, t in p.top_ops(region))
        print(f"{region:<12}{ms:>10.3f} {100 * ms / busy:6.2f}%  {tops}")
    print("\nlongest operations by name (ms per step), split by scope and pass")
    split: dict = {}
    for o in p.ops:
        by = split.setdefault(o.name, {})
        by[(o.scope, o.phase)] = by.get((o.scope, o.phase), 0.0) + o.self_ns
    for name, by in sorted(split.items(), key=lambda kv: -sum(kv[1].values()))[:10]:
        parts = ", ".join(f"{s}/{ph} {per(v):.3f}" for (s, ph), v in
                          sorted(by.items(), key=lambda kv: -kv[1])[:6])
        print(f"{name:<40}{per(sum(by.values())):>10.3f}  {parts}")
    print("\nidle gaps (ms, program span at the middle)")
    for d, name in p.gaps:
        print(f"{d / 1e6:10.3f}  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
