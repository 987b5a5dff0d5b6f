"""The train step's named scopes and their reduction.

On the CPU: the test configuration's compiled train step carries every
scope in its HLO ``op_name`` metadata, each matmul and sort lies under a
layer's scope, and the scopes change nothing but the metadata.  On a
profile recorded on a v5e chip (``v5e_tiny_train.trace.json.gz``: three
traced train steps of the test configuration in the mask-learning phase,
the run's ``.trace.json.gz`` as the harness's ``trace.Capture`` wrote it,
less each operation's source location):
the reduction puts every device operation in one region and counts the
steps by the program's ``train.step`` spans.
"""
from __future__ import annotations

import contextlib
import gzip
import json
import re
import shutil
import types

import jax
import pytest

from bench.harness import cells, scopes, spec, train
from bench.tests import tiny

CONFIG = spec.load_json(tiny.DATA / "configs" / "tiny-2of4.json")
MIX = spec.load_json(tiny.DATA / "mixes" / "tiny-train.json")
FIXTURE = tiny.DATA / "v5e_tiny_train.trace.json.gz"
# ``trace.reduce`` of the same run's ``.xplane.pb`` (2.2 MB, not kept), on
# the chip: the export holds every device operation the xplane holds
XPLANE_BUSY_S = 0.001337489
LAYER_SCOPES = {"nm_mask", "attention", "mlp", "lm_head", "optimizer"}
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _compiled_step() -> str:
    job = train.Job(CONFIG, MIX, 1)
    return job.trainer._step.lower(job.state, job._batch()).compile().as_text()


def _computations(hlo: str) -> str:
    """The compiled module without its metadata and stack-frame tables."""
    head, _, rest = hlo.partition("\nFileNames\n")
    rest = rest.partition("\nStackFrames\n")[2].split("\n\n", 1)[-1]
    return re.sub(r",? metadata=\{[^}]*\}", "", head + "\n" + rest)


@pytest.fixture(scope="module")
def hlo() -> str:
    return _compiled_step()


def test_every_scope_names_operations_of_the_step(hlo):
    found = {scopes.classify(p)[0] for p in _OP_NAME.findall(hlo)}
    assert set(scopes.SCOPES) <= found


def test_matmuls_and_sorts_lie_under_a_layer_scope(hlo):
    # the step has matmuls; since the N:M mask ranks by compares it has no
    # sort of its own, and any sort left must still lie under a layer scope
    ops = re.findall(r" (dot|convolution|sort)\(.*op_name=\"([^\"]*)\"", hlo)
    assert "dot" in {op for op, _ in ops}
    for op, path in ops:
        scope = scopes.classify(path)[0]
        assert scopes.PARENT.get(scope, scope) in LAYER_SCOPES, (op, path)


def test_remat_recompute_and_backward_show_in_the_paths(hlo):
    phases = {scopes.classify(p) for p in _OP_NAME.findall(hlo)}
    for scope in ("attention", "mlp"):
        assert {(scope, x) for x in scopes.PASSES} <= phases


def test_scopes_change_nothing_but_metadata(hlo, monkeypatch):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _compiled_step()
    assert {scopes.classify(p)[0] for p in _OP_NAME.findall(bare)} == \
        {scopes.UNSCOPED}
    assert _computations(bare) == _computations(hlo)


@pytest.mark.parametrize("path, expect", [
    ("jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "mlp/dot_general:", ("mlp", "backward")),
    ("jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/while/body/closed_call/"
     "bqhgd,bkhd->bhgqk/dot_general", ("attention", "recompute")),
    ("jit(step)/jvp(layers)/while/body/closed_call/add", ("layers", "forward")),
    ("jit(step)/nm_mask/cond/branch_1_fun/top_k:", ("nm_mask", "forward")),
    ("jit(step)/optimizer/autoswitch/reduce_sum", ("autoswitch", "forward")),
    ("jit(step)/transpose(jvp(lm_head))/mul;jit(step)/transpose(jvp(lm_head))",
     ("lm_head", "backward")),
    ("jit(step)/jit(_threefry_fold_in)/slice", ("unscoped", "forward")),
    ("", ("unscoped", "forward")),
])
def test_an_operation_goes_to_its_innermost_scope(path, expect):
    assert scopes.classify(path) == expect


@pytest.fixture(scope="module")
def profile(tmp_path_factory):
    d = tmp_path_factory.mktemp("scopes")
    shutil.copy(FIXTURE, d / FIXTURE.name)
    return scopes.load(str(d))


def test_regions_sum_to_the_busy_time(profile):
    assert profile is not None and profile.scoped()
    total = sum(profile.region_ms(r) for r in scopes.REGIONS)
    assert total == pytest.approx(profile.per_step_ms(profile.busy_ns),
                                  rel=1e-6)
    table = profile.table()
    assert sum(sum(row.values()) for row in table.values()) == \
        pytest.approx(total, rel=1e-6)
    assert profile.region_ms("unscoped") < 0.05 * total


@pytest.mark.parametrize("region,ms", [
    ("nm_mask", 0.3695364060000057), ("attention", 0.03154554666672398),
    ("mlp", 0.003564870000009735), ("layers", 0.007494270666641494),
    ("lm_head", 0.006589402000012497), ("optimizer", 0.017506404666682084),
    ("unscoped", 0.009787553999910752),
])
def test_regions_read_what_they_read_before_scopes_were_declared(profile,
                                                                 region, ms):
    assert profile.region_ms(region) == ms  # to the last digit


def test_export_busy_matches_the_xplane(profile):
    assert profile.busy_ns / 1e9 == pytest.approx(XPLANE_BUSY_S, rel=0.005)


def test_steps_are_the_program_spans_in_the_window(profile):
    assert profile.steps == 3
    assert 0 < profile.dispatch_ms() < profile.window_ns / 1e6
    assert all(name in ("train.step", "idle") for _, name in profile.gaps)


def _ctx(traced: bool, nested=None):
    return types.SimpleNamespace(trace=object() if traced else None,
                                 nested=nested or {})


def test_readers_read_nothing_from_an_untraced_run(monkeypatch, profile):
    monkeypatch.setattr(scopes, "load", lambda d, nested: profile)
    assert scopes.per_step(_ctx(False), "attention") is None
    assert scopes.dispatch(_ctx(False)) is None
    assert scopes.per_step(_ctx(True), "attention") == \
        profile.region_ms("attention")
    assert scopes.dispatch(_ctx(True)) == profile.dispatch_ms()


def test_readers_read_nothing_from_a_program_without_scopes(monkeypatch,
                                                            profile):
    bare = scopes.Profile(
        profile.window_ns, profile.busy_ns,
        [scopes.Op(o.name, o.start, o.dur, o.device) for o in profile.ops],
        [], profile.gaps, profile.devices)
    monkeypatch.setattr(scopes, "load", lambda d, nested: bare)
    assert scopes.per_step(_ctx(True), "attention") is None
    assert scopes.dispatch(_ctx(True)) is None


# a synthetic export: one step of four device operations, one of them (and
# its backward) under a scope ``router`` that the program opens in ``mlp``
ROUTER = "jit(step)/{}(layers)/while/body/closed_call/mlp/router/dot_general:"
_EVENTS = [
    {"ph": "M", "name": "process_name", "pid": 1,
     "args": {"name": "/device:TPU:0"}},
    {"ph": "M", "name": "thread_name", "pid": 1, "tid": 2,
     "args": {"name": "XLA Ops"}},
    {"ph": "X", "name": "bench.window", "pid": 9, "tid": 1, "ts": 0,
     "dur": 1000},
    {"ph": "X", "name": "train.step", "pid": 9, "tid": 1, "ts": 5, "dur": 4},
    {"ph": "X", "name": "fusion.1", "pid": 1, "tid": 2, "ts": 100, "dur": 100,
     "args": {"tf_op": "jit(step)/jvp(layers)/while/body/mlp/dot_general:"}},
    {"ph": "X", "name": "fusion.2", "pid": 1, "tid": 2, "ts": 250, "dur": 30,
     "args": {"tf_op": ROUTER.format("jvp")}},
    {"ph": "X", "name": "fusion.3", "pid": 1, "tid": 2, "ts": 300, "dur": 20,
     "args": {"tf_op": ROUTER.format("transpose(jvp") + ")"}},
    {"ph": "X", "name": "fusion.4", "pid": 1, "tid": 2, "ts": 400, "dur": 200,
     "args": {"tf_op": "jit(step)/jvp(layers)/while/body/attention/dot:"}},
]
ROUTER_METRIC = """from bench.harness import scopes

SCOPE = ("router", "mlp")


def read(ctx):
    return scopes.per_step(ctx, "router")
"""


@pytest.fixture
def router_root(tmp_path, monkeypatch):
    """A checkout with a metric file that declares ``router`` in ``mlp``,
    whose last traced run left the synthetic export."""
    root = tiny.make_root(tmp_path)
    (root / "bench/metrics/router_ms.train.py").write_text(ROUTER_METRIC)
    traces = tmp_path / "traces"
    traces.mkdir()
    with gzip.open(traces / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": _EVENTS}, f)
    monkeypatch.setattr(cells, "TRACE_DIR", traces)
    return root


def test_a_declared_scope_takes_its_operations_out_of_its_parent(router_root):
    reader = spec.metric_reader("router_ms.train", router_root)
    nested = scopes.declared([reader])
    assert nested == {"router": "mlp"}
    ms = lambda us: us * 1e3 / 1e6  # one step; the export counts in us
    bare = scopes.per_step(_ctx(True), "mlp")
    assert bare == pytest.approx(ms(150))  # no metric declares it
    assert reader.read(_ctx(True, nested)) == pytest.approx(ms(50))
    assert scopes.per_step(_ctx(True, nested), "mlp") == pytest.approx(ms(100))
    p = scopes.load(str(cells.TRACE_DIR), nested)
    assert p.regions[-2:] == ("router", "unscoped")
    assert sum(p.region_ms(r) for r in p.regions) == pytest.approx(ms(350))
    assert p.table()["router"]["backward"] == pytest.approx(ms(20))
    # a declared scope that no operation carries reads nothing
    assert scopes.per_step(_ctx(True, {"experts": "mlp"}), "experts") is None


@pytest.mark.parametrize("declare", [
    [("mlp", "layers")],  # a scope the reduction knows
    [("router", "mlp"), ("router", "attention")],  # two parents
    [("router", "unscoped")],  # no region
    [("router", "dispatch"), ("dispatch", "router")],  # a loop
])
def test_a_scope_must_nest_in_a_region_once(declare):
    readers = [types.SimpleNamespace(SCOPE=d, __name__=f"m{i}")
               for i, d in enumerate(declare)]
    with pytest.raises(ValueError):
        scopes.declared(readers)
