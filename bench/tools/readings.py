"""Readings that a training cell's correctness limits are set from, on the chip.

    python3 bench/tools/readings.py --workload gpt2-step-train \
        --seeds 1,2,3 [--seconds 2] [--fault half_batch] [--control] [--memory]

Runs the cell as ``run.py`` does, once per seed in one process (the
compile cache is warm after the first), with an optional fault planted in
the timed path (``harness/faults.py``).  Each seed prints one JSON line:
the program's compared numbers and the run's ``correct``; the change's
worst leaf at several trims; where the program's largest changes sit
against the two sides' frozen preconditioners P* (the look behind the
trim); and with ``--control`` the control (the reference one precision
step down, fp8, in the program's place) judged by the harness's own check.
``--memory`` first compiles the train step at the mix's batch and at twice
it, and prints each compiled program's memory analysis beside the
device's memory statistics after one step at the mix's batch.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

TRIMS = (0.0, 0.001, 0.01, 0.03)


def _analysis(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {k: getattr(ma, k) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes",
        "generated_code_size_in_bytes") if hasattr(ma, k)}


def memory(cell, devs) -> None:
    import jax

    from bench.harness import train

    job = train.Job(cell.config, cell.mix, 1)
    batch = job._batch()
    out = {"memory": True}
    for mult in (1, 2):
        spec = {k: jax.ShapeDtypeStruct((v.shape[0] * mult,) + v.shape[1:],
                                        v.dtype) for k, v in batch.items()}
        t0 = time.perf_counter()
        try:
            c = job.trainer._step.lower(job.state, spec).compile()
            out[f"batch_{cell.mix['batch'] * mult}"] = {
                **_analysis(c), "compile_s": time.perf_counter() - t0}
        except Exception as e:  # a batch that does not fit is a reading
            out[f"batch_{cell.mix['batch'] * mult}"] = {"error": repr(e)[:400]}
    job.state, met = job.trainer._step(job.state, batch)
    jax.block_until_ready(met["loss"])
    out["stats_after_one_step"] = devs[0].memory_stats()
    print(json.dumps(out), flush=True)
    job.close()


def look(prog: dict, ref: dict, leaf: str) -> dict:
    """Where the program's largest changes of one leaf sit: the ratio of
    the program's P* to the reference's on its largest 1% of |change|
    and on the rest, and the share of the squared gap they hold."""
    pc, rc = prog["change"][leaf].ravel(), ref["change"][leaf].ravel()
    pp, rp = prog["precond"][leaf].ravel(), ref["precond"][leaf].ravel()
    n = max(1, int(0.01 * pc.size))
    top = np.zeros(pc.size, bool)
    top[np.argpartition(np.abs(pc), pc.size - n)[pc.size - n:]] = True
    ratio = pp / rp
    d2 = np.square(pc - rc)
    q = lambda x: [float(v) for v in np.quantile(x, [0.1, 0.5, 0.9])]
    return {"leaf": leaf, "size": int(pc.size),
            "p_ratio_top1pct_q10_50_90": q(ratio[top]),
            "p_ratio_rest_q10_50_90": q(ratio[~top]),
            "ref_p_top_over_leaf_median": float(np.median(rp[top])
                                                / np.median(rp)),
            "gap_sq_share_top1pct": float(d2[top].sum() / d2.sum())}


def trims(prog: dict, ref: dict) -> dict:
    from bench.harness import train

    out = {}
    for t in TRIMS:
        g = train.compare(prog, ref, t)
        out[str(t)] = [g["change"], g["change_leaf"]]
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--fault", default="none")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--memory", action="store_true")
    args = ap.parse_args()

    from bench.harness import cells, device, faults, spec, train
    from bench.run import enable_cache

    enable_cache()
    cell = spec.load_cell(args.workload)
    devs = device.require(cell.chips)
    peaks = device.peaks(devs[0].device_kind)
    cfg, mix = cell.config, cell.mix
    lim = cfg["limits"]
    if args.memory:
        memory(cell, devs)
    for seed in (int(s) for s in args.seeds.split(",")):
        keep: dict = {"precond": True}
        t0 = time.perf_counter()
        with faults.FAULTS[args.fault]():
            res = cells.run(cell, devs, peaks, seed, args.seconds, False, t0,
                            keep)
        prog, ref, gaps = keep["checked"], keep["ref"], keep["gaps"]
        every = train.compare(prog, ref, 0.0)
        line = {"seed": seed, "fault": args.fault, "correct": res["correct"],
                "checks": res["checks"],
                "gaps": {k: gaps[k] for k in ("loss", "grad", "change",
                                              "change_leaf", "change_all")},
                "change_by_trim": trims(prog, ref),
                "look": look(prog, ref, every["change_leaf"]),
                "losses": [prog["losses"], ref["losses"]],
                "memory_peak_bytes": res["device"]["memory_peak_bytes"],
                "metrics": res["metrics"]}
        t1 = time.perf_counter()
        if args.control:
            ctl = spec.reference(cfg).train_steps(
                cfg, seed, keep["batches"], train.opt_args(mix), mode="fp8",
                rows_per_block=mix["reference_rows"])
            g = train.compare(ctl, ref, lim["train_change_trim"])
            correct, checks = train.judge(g, ctl["phase2"], lim)
            line["control"] = {
                "correct": correct, "checks": checks,
                "gaps": {k: g[k] for k in ("loss", "grad", "change",
                                           "change_leaf", "change_all")},
                "change_by_trim": trims(ctl, ref), "losses": ctl["losses"]}
        line["control_s"] = time.perf_counter() - t1
        line["wall_s"] = time.perf_counter() - t0
        print(json.dumps(line, default=float), flush=True)


if __name__ == "__main__":
    main()
