"""Device time of the train step by the program's named scopes.

The program names each layer of its train step with ``jax.named_scope``
(``nm_mask``, ``layers``, ``attention``, ``mlp``, ``lm_head``,
``optimizer`` and, inside it, ``autoswitch``) and opens a host span
``train.step`` around each call of the step.  A traced run's profile
directory holds, beside the ``.xplane.pb`` that ``trace.reduce`` reads, the
profiler's trace-viewer export ``<host>.trace.json.gz``: there every device
operation (``XLA Ops`` line of a ``/device:TPU:<n>`` process) carries its
HLO ``op_name`` as ``args.tf_op``, a path such as
``jit(step)/transpose(jvp(layers))/while/body/checkpoint/mlp/dot_general``.

- the window: the ``bench.window`` span; only operations inside it count,
  clipped to it, at self time (``trace._nest``), averaged over the devices
  that ran any;
- an operation's scope: the innermost known scope on its path, once the
  transformation wrappers (``jvp(...)``, ``transpose(...)``) are taken
  off; none is ``unscoped``;
- its pass: ``recompute`` where the path runs through the remat's
  ``rematted_computation``, else ``backward`` where it runs through
  ``transpose(``, else ``forward``;
- steps: the ``train.step`` spans that start inside the window.

``region_ms`` folds ``autoswitch`` into ``optimizer``.  A run that was not
traced, whose trace has no device operation (as on a CPU), or whose
program emits no ``train.step`` span or known scope reads ``None``.

The known scopes are ``SCOPES`` and those that the cell's metric files
declare (``declared``): a metric file may set ``SCOPE = (name, parent)``,
a scope of the program's and the region it nests in.  Each declared scope
is a region of its own, so its operations leave the parent's region, and
the regions still sum to busy; a declared scope that no operation carries
reads ``None``.
"""
from __future__ import annotations

import dataclasses
import functools
import gzip
import json
import os
import re
import statistics
from typing import Optional

from bench.harness import trace

STEP_SPAN = "train.step"
SCOPES = ("nm_mask", "layers", "attention", "mlp", "lm_head", "optimizer",
          "autoswitch")
UNSCOPED = "unscoped"
REGIONS = ("nm_mask", "attention", "mlp", "layers", "lm_head", "optimizer",
           UNSCOPED)
PARENT = {"autoswitch": "optimizer"}
PASSES = ("forward", "backward", "recompute")
_WRAPPER = re.compile(r"^(?:[\w.-]+\()+|\)+$")
_DEVICE = re.compile(r"/device:TPU:(\d+)")


@dataclasses.dataclass
class Op(trace.Op):
    scope: str = UNSCOPED  # innermost known scope (``autoswitch`` kept)
    phase: str = "forward"


@dataclasses.dataclass
class Profile:
    window_ns: float
    busy_ns: float  # averaged over the devices
    ops: list  # Op inside the window, every device
    step_spans: list  # (start, dur) ns of each train.step in the window
    gaps: list  # the 10 longest (ns, train.step or idle) on the first device
    devices: int
    nested: dict = dataclasses.field(default_factory=dict)  # declared

    @property
    def known(self) -> tuple:
        return SCOPES + tuple(self.nested)

    @property
    def regions(self) -> tuple:
        return REGIONS[:-1] + tuple(self.nested) + (UNSCOPED,)

    @property
    def steps(self) -> int:
        return len(self.step_spans)

    def scoped(self) -> bool:
        return any(o.scope != UNSCOPED for o in self.ops)

    def per_step_ms(self, ns: float) -> float:
        return ns / 1e6 / self.devices / self.steps

    def region_ms(self, region: str) -> float:
        """Device self time per step in one region (``regions``)."""
        return self.per_step_ms(sum(
            o.self_ns for o in self.ops
            if PARENT.get(o.scope, o.scope) == region))

    def table(self) -> dict:
        """``{scope: {pass: ms per step}}`` over the known scopes and
        unscoped."""
        out = {s: dict.fromkeys(PASSES, 0.0) for s in self.known + (UNSCOPED,)}
        for o in self.ops:
            out[o.scope][o.phase] += self.per_step_ms(o.self_ns)
        return out

    def top_ops(self, region: str, n: int = 5) -> list:
        """``[(HLO name, ms per step)]`` of one region by self time."""
        tot: dict = {}
        for o in self.ops:
            if PARENT.get(o.scope, o.scope) == region:
                tot[o.name] = tot.get(o.name, 0.0) + o.self_ns
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [(k, self.per_step_ms(v)) for k, v in best]

    def dispatch_ms(self) -> float:
        """Median host time of one ``train.step`` call."""
        return statistics.median(d for _, d in self.step_spans) / 1e6


def declared(readers) -> dict:
    """``{scope: parent}`` that metric reader modules declare as
    ``SCOPE = (scope, parent)``; the parent is a region, or a declared scope
    that leads to one."""
    out: dict = {}
    for r in readers:
        if not hasattr(r, "SCOPE"):
            continue
        name, parent = r.SCOPE
        if name in SCOPES or name == UNSCOPED or out.get(name, parent) != parent:
            raise ValueError(f"scope {name!r} in {parent!r} ({r.__name__}) is "
                             "a known scope or declared twice")
        out[name] = parent
    for name in out:
        up, seen = name, {name}
        while up in out:
            up = out[up]
            if up in seen:
                raise ValueError(f"scopes nest in a loop: {sorted(seen)}")
            seen.add(up)
        if up not in REGIONS[:-1]:
            raise ValueError(f"scope {name!r} nests in {up!r}, no region")
    return out


def classify(path: str, known=SCOPES) -> tuple[str, str]:
    """``(scope, pass)`` of one operation from its ``tf_op`` path
    (``op_name``, in the export followed by ``:`` and the op type)."""
    path = path.split(";", 1)[0]
    if ":" in path:
        path = path.rpartition(":")[0]
    scope = UNSCOPED
    for part in path.split("/"):
        name = _WRAPPER.sub("", part)
        if name in known:
            scope = name
    if "rematted_computation" in path:
        phase = "recompute"
    elif "transpose(" in path:
        phase = "backward"
    else:
        phase = "forward"
    return scope, phase


def find(directory: str) -> Optional[str]:
    """The trace-viewer export under a traced run's profile directory."""
    for base, _, files in os.walk(directory):
        for f in sorted(files):
            if f.endswith(".trace.json.gz"):
                return os.path.join(base, f)
    return None


def load(directory: str, nested: Optional[dict] = None) -> Optional[Profile]:
    """The profile of the traced run whose directory is given, parsed once
    per file and set of ``nested`` scopes (``declared``); ``None`` where
    there is no export or no device operation."""
    path = find(directory)
    if path is None:
        return None
    return _parse(path, os.stat(path).st_mtime_ns,
                  tuple((nested or {}).items()))


@functools.lru_cache(maxsize=1)
def _parse(path: str, _mtime_ns: int, nested: tuple) -> Optional[Profile]:
    known = SCOPES + tuple(n for n, _ in nested)
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    devices, xla_ops = {}, set()
    for e in events:
        if e.get("ph") != "M":
            continue
        if e["name"] == "process_name":
            m = _DEVICE.fullmatch(e["args"]["name"])
            if m:
                devices[e["pid"]] = int(m.group(1))
        elif e["name"] == "thread_name" and e["args"]["name"] == "XLA Ops":
            xla_ops.add((e["pid"], e["tid"]))
    ns = lambda us: us * 1e3
    window, steps, dev_ev = None, [], []
    for e in events:
        if e.get("ph") != "X":
            continue
        if (e["pid"], e.get("tid")) in xla_ops and e["pid"] in devices:
            dev_ev.append(e)
        elif e["name"] == trace.SPAN and window is None:
            window = (ns(e["ts"]), ns(e["ts"] + e["dur"]))
        elif e["name"] == STEP_SPAN:
            steps.append((ns(e["ts"]), ns(e["dur"])))
    if window is None or not dev_ev:
        return None
    t0, t1 = window
    by_dev: dict = {}
    for e in dev_ev:
        s, end = ns(e["ts"]), ns(e["ts"] + e["dur"])
        if end <= t0 or s >= t1:
            continue
        s, end = max(s, t0), min(end, t1)
        args = e.get("args", {})
        scope, phase = classify(args.get("tf_op", ""), known)
        dev = devices[e["pid"]]
        name = trace._op_name(args.get("long_name") or e["name"])
        by_dev.setdefault(dev, []).append(
            Op(name, s, end - s, dev, scope=scope, phase=phase))
    steps = [(s, d) for s, d in steps if t0 <= s < t1]
    ops, busy, gaps = [], 0.0, []
    for dev in sorted(by_dev):
        trace._nest(by_dev[dev])
        ops += by_dev[dev]
        u = trace._union([(o.start, o.start + o.dur) for o in by_dev[dev]])
        busy += sum(e - s for s, e in u)
        if dev == min(by_dev):
            edges = [t0] + [x for iv in u for x in iv] + [t1]
            gaps = sorted(((b - a, a, b) for a, b in zip(edges[::2], edges[1::2])
                           if b > a), reverse=True)[:10]
    spans = [(s, s + d, STEP_SPAN) for s, d in steps]
    n = max(1, len(by_dev))
    return Profile(
        window_ns=t1 - t0, busy_ns=busy / n, ops=ops, step_spans=steps,
        gaps=[(d, trace._host_at(spans, (a + b) / 2)) for d, a, b in gaps],
        devices=n, nested=dict(nested))


def per_step(ctx, region: str) -> Optional[float]:
    """A region metric's reading for a run's ``Ctx``: device self time per
    step in ms, or ``None`` where the run was not traced or its program
    names no scope or step, or not the declared scope that is read."""
    p = _profile(ctx)
    if p is None or not p.scoped():
        return None
    if region in p.nested and all(o.scope != region for o in p.ops):
        return None
    return p.region_ms(region)


def dispatch(ctx) -> Optional[float]:
    p = _profile(ctx)
    return p.dispatch_ms() if p is not None else None


def _profile(ctx) -> Optional[Profile]:
    from bench.harness import cells

    if ctx.trace is None:
        return None
    p = load(str(cells.TRACE_DIR), ctx.nested)
    return p if p is not None and p.steps else None
