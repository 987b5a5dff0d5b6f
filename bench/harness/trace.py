"""Device profile of the measured window, reduced to what the metrics read.

A traced run wraps its window in ``jax.profiler`` and in a host span named
``bench.window``.  The reduction reads the ``.xplane.pb`` with nothing but
JAX (``jax.profiler.ProfileData``):

- the window: the ``bench.window`` span on the host plane (the device and
  host planes share the profiler's clock);
- device busy time: the union of the intervals in which an operation ran
  on a device (``XLA Ops`` line), inside the window, averaged over chips;
- per-operation time: an operation's event name is its HLO text, ``%name
  = shape op(operands)``, and it is counted under ``name`` (a Pallas
  kernel's name is its kernel function) by self time, so a layer
  ``while`` does not count the operations inside it twice;
- idle gaps: the spaces between busy intervals, each named by the
  harness's host span (``bench.*``) that was open at its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
from typing import Optional

SPAN = "bench.window"
_SUFFIX = re.compile(r"\.\d+$")


def options():
    import jax

    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0  # the harness's own spans are TraceAnnotations
    return o


class Capture:
    """``start()`` before the window, ``stop()`` after; ``path`` is the
    profile written.  The directory is emptied first."""

    def __init__(self, directory: str):
        self.dir = directory
        self.path: Optional[str] = None
        self._span = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir, profiler_options=options())
        self._span = jax.profiler.TraceAnnotation(SPAN)
        self._span.__enter__()

    def stop(self) -> str:
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        self.path = files[0]
        return self.path


@dataclasses.dataclass
class Op:
    name: str  # the HLO instruction's name without its number
    start: float  # ns on the profiler's clock
    dur: float  # ns
    device: int
    self_ns: float = 0.0  # dur minus the operations nested inside it


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # averaged over the devices
    ops: list  # Op inside the window
    gaps: list  # the 10 longest (seconds, host activity) on device 0
    devices: int

    def top_ops(self, n: int = 10) -> list:
        """Operations by total self time."""
        tot: dict = {}
        for o in self.ops:
            tot[o.name] = tot.get(o.name, 0.0) + o.self_ns
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9 / max(1, self.devices)] for k, v in best]


def _op_name(text: str) -> str:
    return _SUFFIX.sub("", text.split(" = ", 1)[0].lstrip("%"))


def _nest(ops: list) -> None:
    """Set each operation's self time: an operation that contains others
    (a ``while`` over layers) keeps only the time nothing inside ran."""
    stack: list = []
    for o in sorted(ops, key=lambda o: (o.start, -o.dur)):
        o.self_ns = o.dur
        while stack and stack[-1].start + stack[-1].dur <= o.start:
            stack.pop()
        if stack:
            stack[-1].self_ns -= o.dur
        stack.append(o)


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _device_index(name: str) -> Optional[int]:
    m = re.fullmatch(r"/device:TPU:(\d+)", name)
    return int(m.group(1)) if m else None


def reduce(path: str, n_devices: int) -> Summary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, host = [], []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == SPAN:
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                elif ev.name.startswith("bench."):
                    host.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name))
    if not spans:
        raise RuntimeError(f"no {SPAN} span in {path}")
    t0, t1 = spans[0]
    ops = []
    for plane in pd.planes:
        dev = _device_index(plane.name)
        if dev is None or dev >= n_devices:
            continue
        dev_ops = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= t0 or s >= t1:
                    continue
                s, e = max(s, t0), min(e, t1)
                dev_ops.append(Op(_op_name(ev.name), s, e - s, dev))
        _nest(dev_ops)
        ops += dev_ops
    busy, gaps = 0.0, []
    for dev in range(n_devices):
        u = _union([(o.start, o.start + o.dur) for o in ops if o.device == dev])
        busy += sum(e - s for s, e in u)
        if dev == 0:
            edges = [t0] + [x for iv in u for x in iv] + [t1]
            gaps = sorted(((b - a, a, b) for a, b in zip(edges[::2], edges[1::2])
                           if b > a), reverse=True)[:10]
    return Summary(
        window_s=(t1 - t0) / 1e9,
        busy_s=busy / 1e9 / n_devices,
        ops=ops,
        gaps=[(d / 1e9, _host_at(host, (a + b) / 2)) for d, a, b in gaps],
        devices=n_devices,
    )


def _host_at(host: list, t: float) -> str:
    """The innermost harness span open at ``t`` (``idle`` if none)."""
    best = None
    for s, e, name in host:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "idle"


def breakdown(summary: Summary) -> dict:
    """The ``breakdown`` of a traced run's result line."""
    return {
        "device_ops": summary.top_ops(10),
        "idle_gaps": [[name, d] for d, name in summary.gaps[:10]],
    }
