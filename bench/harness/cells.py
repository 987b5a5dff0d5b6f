"""One run of one cell: set up, measure, check, and build the result line.

The mix's ``kind`` picks the runner; ``train`` drives the STEP train step.
Each run prints diagnostic JSON lines first (compiles in the window,
set-up parts, the checked gaps) and returns the contract's result object,
whose ``checks`` key comes last.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import time
from typing import Optional

from bench.harness import device, scopes, spec, trace

clock = time.perf_counter
TRACE_DIR = spec.BENCH_DIR / ".traces"


class Compiles:
    """Counts the executables JAX compiles or loads from its persistent
    cache (every compile request), so a compile in the window shows."""

    def __init__(self):
        import jax.monitoring as mon

        self.n = 0
        mon.register_event_listener(self._event)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.n += 1


@dataclasses.dataclass
class Ctx:
    """What a per-layer metric reader gets."""

    config: dict
    mix: dict
    peaks: dict
    start: float  # window on the host clock
    end: float
    trace: Optional[trace.Summary]
    extra: dict  # runner-specific numbers (the training rate)
    # the program's scopes that the cell's metric files declare, each with
    # the region it nests in (``scopes.declared``)
    nested: dict = dataclasses.field(default_factory=dict)


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _metric(value: float, unit: str) -> dict:
    return {"value": value, "unit": unit}


def _per_layer(cell: spec.Cell, ctx: Ctx) -> dict:
    readers = [spec.metric_reader(m["name"], cell.root) for m in cell.per_layer]
    ctx = dataclasses.replace(ctx, nested=scopes.declared(readers))
    out = {}
    for m, reader in zip(cell.per_layer, readers):
        v = reader.read(ctx)
        if v is not None:
            out[m["name"]] = _metric(float(v), m["unit"])
    return out


def run(cell: spec.Cell, devs: list, peaks: dict, seed: int, seconds: float,
        traced: bool, t_start: float, keep: Optional[dict] = None) -> dict:
    """One run; ``keep`` (tools and tests) receives what the check
    compared: the checked training numbers and the reference's."""
    kind = cell.mix["kind"]
    keep = {} if keep is None else keep
    if kind == "train":
        return train_cell(cell, devs, peaks, seed, seconds, traced, t_start,
                          keep)
    raise ValueError(f"unknown mix kind {kind!r}")


def _result(cell, devs, correct, attempted, failed, metrics, checks,
            summary: Optional[trace.Summary], mem: int) -> dict:
    dev = device.describe(devs)
    dev["memory_peak_bytes"] = mem
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": dev}
    if summary is not None:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        out["breakdown"] = trace.breakdown(summary)
    out["checks"] = checks
    return out


def _free() -> None:
    import jax

    gc.collect()
    jax.clear_caches()


def train_cell(cell, devs, peaks, seed, seconds, traced, t_start,
               keep) -> dict:
    from bench.harness import train

    cfg, mix = cell.config, cell.mix
    compiles = Compiles()
    t0 = clock()
    job = train.Job(cfg, mix, seed)
    t_job = clock() - t0
    # set-up: compiles, then the checked steps
    checked = job.first_steps(precond=keep.get("precond", False))
    t_first = clock() - t0 - t_job
    cap = trace.Capture(str(TRACE_DIR)) if traced else None
    c0 = compiles.n
    setup_s = clock() - t_start
    if cap is not None:
        cap.start()
    win = job.window(seconds)
    if cap is not None:
        cap.stop()
    in_window = compiles.n - c0
    mem = device.memory_peak_bytes(devs)
    summary = trace.reduce(cap.path, len(devs)) if cap is not None else None
    rate = win["tokens"] / (win["end"] - win["start"])
    ctx = Ctx(cfg, mix, peaks, win["start"], win["end"], summary,
              {"train_tokens_per_s": rate})
    if traced:
        metrics = _per_layer(cell, ctx)
    else:
        metrics = {"setup_s": _metric(setup_s, "s"),
                   "train_tokens_per_s": _metric(rate, "tokens/s")}
        metrics = {m["name"]: metrics[m["name"]] for m in cell.end_to_end}
    emit({"diag": "train", "compiles_in_window": in_window,
          "setup_s": setup_s, "window_s": win["end"] - win["start"],
          "steps": win["steps"], "batch": mix["batch"], "job_s": t_job,
          "first_steps_s": t_first, "losses": checked["losses"],
          "phase2": checked["phase2"]})
    job.close()
    _free()
    keep["checked"], keep["batches"] = checked, job.checked_batches()
    t0 = clock()
    ref = spec.reference(cfg).train_steps(cfg, seed, keep["batches"],
                                          train.opt_args(mix),
                                          rows_per_block=mix["reference_rows"])
    lim = cfg["limits"]
    gaps = train.compare(checked, ref, lim["train_change_trim"])
    keep["ref"], keep["gaps"] = ref, gaps
    emit({"diag": "reference", "seconds": clock() - t0,
          "left_out": gaps["left_out"]})
    emit({"diag": "train_gaps", **{k: gaps[k] for k in (
        "loss", "grad", "change", "change_leaf", "change_all")}})
    correct, checks = train.judge(gaps, checked["phase2"], lim)
    return _result(cell, devs, correct, win["steps"], 0, metrics, checks,
                   summary, mem)
