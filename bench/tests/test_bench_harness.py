"""The harness's own logic on the CPU: finding pieces by name, the
yardstick's counts, the check's arithmetic and the run's exits."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench.harness import costs, device, spec, train
from bench.tests import tiny


def test_every_cell_loads_by_name():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], bench)
        assert cell.mix["kind"] == "train"
        assert cell.config["name"] == w["config"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]).read)
        assert hasattr(spec.reference(cell.config), "train_steps")


def test_benchmark_file_names_its_pieces():
    bench = spec.load_benchmark()
    for c in bench["configs"]:
        assert (spec.ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["per_layer"]:
        assert (spec.BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()


def test_new_cell_mix_and_metric_are_only_new_files(tmp_path):
    """A later change adds a mix, a metric and a cell as new files plus
    new entries; the harness finds them with no edit of its code."""
    root = tiny.make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    mix = json.loads((root / "bench/mixes/tiny-train.json").read_text())
    mix["seq_len"] = 64
    (root / "bench/mixes/tiny-long.json").write_text(json.dumps(mix))
    (root / "bench/metrics/steps_per_s.long.py").write_text(
        "def read(ctx):\n"
        "    rate = ctx.extra.get('train_tokens_per_s')\n"
        "    return rate / ctx.mix['seq_len'] if rate else None\n")
    bench["workloads"].append({"name": "tiny.long", "config": "tiny-2of4",
                               "traffic": "tiny-long", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][0]["workloads"].append("tiny.long")
    bench["per_layer"].append({
        "name": "steps_per_s.long", "unit": "1/s", "better": "higher",
        "source": "host_clock", "layer": "train step",
        "moves": "train_tokens_per_s", "workloads": ["tiny.long"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("tiny.long", root=root)
    assert cell.mix["seq_len"] == 64
    assert [m["name"] for m in cell.end_to_end] == ["train_tokens_per_s",
                                                    "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["steps_per_s.long"]
    reader = spec.metric_reader("steps_per_s.long", root)
    ctx = type("C", (), {"extra": {"train_tokens_per_s": 640.0},
                         "mix": cell.mix})()
    assert reader.read(ctx) == 10.0
    with pytest.raises(KeyError):
        spec.load_cell("tiny.nothing", root=root)


def test_costs_count_the_published_shapes():
    gpt2 = spec.load_json(spec.BENCH_DIR / "configs" / "gpt2-paper-2of4.json")
    d, v = 768, 50304
    # 12 x (4 d^2 attention + 8 d^2 MLP + 4 d biases + 4 d norms) + V d + 2 d
    assert costs.param_count(gpt2) == 12 * (12 * d * d + 8 * d) + v * d + 2 * d
    assert costs.train_flops_per_token(gpt2, 1024) == pytest.approx(
        6 * costs.param_count(gpt2) + 6 * 12 * 12 * 64 * 1024)


def test_param_count_matches_the_program():
    from bench.harness import system

    gpt2 = spec.load_json(spec.BENCH_DIR / "configs" / "gpt2-paper-2of4.json")
    from repro.models.model import param_count

    assert costs.param_count(gpt2) == param_count(system.arch(gpt2))


@pytest.mark.parametrize("trim,expect", [
    (0.0, 5.0), (0.125, 3.0), (0.25, 0.0)])
def test_trimmed_norm_leaves_out_the_largest_share(trim, expect):
    v = np.array([[0.0, -3.0], [4.0, 0.0], [0.0, 0.0], [0.0, 0.0]])
    # 8 coordinates: an eighth is the largest, a quarter the two largest
    assert train.trimmed_norm(v, trim) == pytest.approx(expect)
    assert train.trimmed_norm(np.ones(100), 0.01) == pytest.approx(99 ** 0.5)


def _numbers(grad=1.0, change=None, losses=(2.0, 1.9, 1.8)):
    rng = np.random.default_rng(0)
    change = change or {k: rng.normal(size=1000) for k in "abc"}
    return {"losses": list(losses), "grad_norms": {"a": grad, "b": 1.0,
                                                   "c": 1e-6},
            "change": change}


def test_compare_takes_the_worst_leaf_and_leaves_out_zero_gradients():
    ref = _numbers()
    same = train.compare(_numbers(change=dict(ref["change"])), ref, 0.01)
    assert same["loss"] == 0 and same["grad"] == 0 and same["change"] == 0
    assert same["left_out"] == ["c"]  # its gradient is nought to rounding
    # one leaf left unmoved reads 1, whatever the others do
    frozen = dict(ref["change"], b=np.zeros(1000))
    g = train.compare(_numbers(grad=1.1, change=frozen), ref, 0.01)
    assert g["change"] == pytest.approx(1.0) and g["change_leaf"] == "b"
    assert g["grad"] == pytest.approx(0.1)
    # a few coordinates blown up on one side are trimmed, and reported
    noisy = dict(ref["change"], a=ref["change"]["a"].copy())
    noisy["a"][:5] = 1e3
    g = train.compare(_numbers(change=noisy), ref, 0.01)
    assert g["change"] < 0.05 < g["change_all"]


@pytest.mark.parametrize("gaps,phase2,correct", [
    ({"loss": 0.1, "grad": 0.001, "change": 0.01}, [0, 0, 1], True),
    ({"loss": 0.1, "grad": 0.02, "change": 0.01}, [0, 0, 1], False),
    ({"loss": 0.1, "grad": 0.001, "change": 0.5}, [0, 0, 1], False),
    ({"loss": 0.1, "grad": 0.001, "change": 0.01}, [0, 0, 0], False),
])
def test_judge_holds_each_number_to_its_limit(gaps, phase2, correct):
    limits = {"train_loss_gap": None, "train_grad_norm_gap": 0.01,
              "train_change_norm_gap": 0.1}
    ok, checks = train.judge(gaps, phase2, limits)
    assert ok is correct
    assert "train_loss_gap" not in checks  # a null limit is not compared
    assert checks["train_grad_norm_gap"] == {"value": gaps["grad"],
                                             "limit": 0.01}
    assert checks["mask_learning_steps"]["value"] == phase2[-1]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        device.peaks("TPU v99")


@pytest.mark.parametrize("stats,peak", [
    ([{"peak_bytes_in_use": 2, "peak_bytes_reserved": 10}], 12),
    ([{"peak_bytes_in_use": 2}, {"peak_bytes_in_use": 5}], 5),
    ([None], 0),
])
def test_memory_peak_counts_what_the_runtime_reserves(stats, peak):
    devs = [type("D", (), {"memory_stats": lambda self, s=s: s})()
            for s in stats]
    assert device.memory_peak_bytes(devs) == peak


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gpt2-step-train",
         "--seed", "4294967301", "--seconds", "1", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_fails_and_prints_no_result():
    r = _run(spec.ROOT)
    assert r.returncode == 2, r.stderr[-2000:]
    assert r.stdout.strip() == ""


def test_run_in_a_bare_directory_fails(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces",
                                                  "__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
