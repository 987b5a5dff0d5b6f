"""The trace reduction on a profile recorded on a v5e chip: three decode
steps of gpt2-paper-2of4 at 4 lanes, cut to 41 ms around them (64 KB)."""
from __future__ import annotations

import gzip

import pytest

from bench.harness import trace
from bench.tests import tiny


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    p = tmp_path_factory.mktemp("trace") / "v5e.xplane.pb"
    p.write_bytes(gzip.open(tiny.DATA / "v5e_gpt2_decode.xplane.pb.gz").read())
    return trace.reduce(str(p), 1)


def test_busy_and_idle_inside_the_window(summary):
    assert summary.window_s == pytest.approx(0.040956518)
    assert 0 < summary.busy_s <= summary.window_s
    assert summary.busy_s == pytest.approx(0.031029552, rel=1e-6)


def test_self_time_does_not_count_nested_operations_twice(summary):
    total = sum(o.self_ns for o in summary.ops) / 1e9
    assert total == pytest.approx(summary.busy_s, rel=1e-6)
    assert all(o.self_ns >= 0 for o in summary.ops)


def test_operations_are_counted_by_name(summary):
    # a Pallas kernel's operations go by its kernel function's name
    attn = [o for o in summary.ops if o.name == "paged_attn_pallas"]
    spmm = [o for o in summary.ops if o.name == "nm_spmm_pallas"]
    assert len(attn) == 38 and len(spmm) == 231
    top = dict(summary.top_ops(50))
    assert top["paged_attn_pallas"] == pytest.approx(
        sum(o.self_ns for o in attn) / 1e9)
    assert sum(top.values()) <= summary.busy_s * (1 + 1e-9)


def test_breakdown_lists_ops_and_gaps(summary):
    b = trace.breakdown(summary)
    assert b["device_ops"][0][0] == "paged_attn_pallas"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(name.startswith("bench.") or name == "idle"
               for name, _ in b["idle_gaps"])
    gaps = [d for _, d in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
