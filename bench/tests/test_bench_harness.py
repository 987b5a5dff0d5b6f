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


GPT2 = spec.BENCH_DIR / "configs" / "gpt2-paper-2of4.json"
MLA_MOE = tiny.DATA / "configs" / "tiny-mla-moe.json"
DSV2 = tiny.DATA / "deepseek-v2-lite.json"


def test_arch_of_gpt2_is_the_config_the_program_ran():
    from repro.configs.base import ArchConfig

    from bench.harness import system

    assert system.arch(spec.load_json(GPT2)) == ArchConfig(
        name="gpt2-paper-2of4", family="dense", n_layers=12, d_model=768,
        n_heads=12, n_kv=12, d_ff=3072, vocab=50304, head_dim=64,
        qkv_bias=True, o_bias=True, mlp="gelu", norm="ln", rope="rope",
        rope_theta=10000.0, local_window=None, tie_embeddings=True,
        param_dtype="bfloat16",
        source="https://huggingface.co/openai-community/gpt2")


def test_gpt2_counts_are_the_same_to_the_bit():
    gpt2 = spec.load_json(GPT2)
    n = 12 * (12 * 768 * 768 + 8 * 768) + 50304 * 768 + 2 * 768
    assert costs.active_param_count(gpt2) == n
    assert costs.train_flops_per_token(gpt2, 1024) == \
        6.0 * n + 6.0 * 12 * 12 * 64 * 1024


def test_arch_builds_latent_attention_and_routed_experts():
    from repro.models.mla import MLAConfig
    from repro.models.model import layer_plan

    from bench.harness import system

    a = system.arch(spec.load_json(MLA_MOE))
    assert (a.family, a.mlp, a.norm, a.tie_embeddings) == \
        ("moe", "swiglu", "rms", False)
    assert a.mla == MLAConfig(kv_lora=32, rope_head_dim=8, nope_head_dim=16,
                              v_head_dim=16)
    assert (a.moe.n_experts, a.moe.top_k, a.moe.d_ff_expert,
            a.moe.n_shared, a.moe.first_layer_dense) == (8, 2, 32, 2, True)
    assert a.moe.capacity_factor == 4.0  # from the program block
    plan = layer_plan(a)
    assert plan.head == ("attn:dense",) and plan.n_body == 2


def _with(path, assumed=(), **model) -> dict:
    config = spec.load_json(path)
    config["model"].update(model)
    config["assumed"] = {k: "test" for k in assumed}
    return config


@pytest.mark.parametrize("path,key,value", [
    (GPT2, "mlp_bias", True),
    (GPT2, "norm_epsilon", 1e-6),
    (GPT2, "rope_scaling", {"type": "linear", "factor": 2.0}),
    (GPT2, "hidden_act", "gelu"),  # exact erf GELU; the program's is tanh
    (GPT2, "n_positions", 1024),  # a key the builder does not know
    (MLA_MOE, "q_lora_rank", 16),
    (MLA_MOE, "norm_topk_prob", False),
    (MLA_MOE, "rms_norm_eps", 1e-5),
    (MLA_MOE, "attention_bias", True),
])
def test_a_model_key_the_program_does_not_compute_raises(path, key, value):
    from bench.harness import system

    with pytest.raises(ValueError, match=key):
        system.arch(_with(path, **{key: value}))
    system.arch(_with(path, assumed=[key], **{key: value}))  # with a reason


@pytest.mark.parametrize("path,model,program", [
    (MLA_MOE, {"first_k_dense_replace": 2}, {}),
    (MLA_MOE, {"moe_layer_freq": 2}, {}),
    (GPT2, {"hidden_act": "relu"}, {}),
    (GPT2, {"norm": "groupnorm"}, {}),
    (MLA_MOE, {"v_head_dim": None}, {}),  # None: the key left out
    (GPT2, {}, {"moe": {"experts_held": 4}}),
])
def test_a_layout_the_program_cannot_build_raises(path, model, program):
    """Even where the file lists the key under ``assumed``."""
    from bench.harness import system

    config = _with(path, assumed=list(model), **model)
    config["model"] = {k: v for k, v in config["model"].items()
                       if v is not None or k not in model}
    config["program"] = program
    with pytest.raises(ValueError):
        system.arch(config)


@pytest.mark.parametrize("path", [MLA_MOE, DSV2])
def test_costs_count_what_the_program_builds(path):
    from repro.models import model as M

    from bench.harness import system

    config = spec.load_json(path)
    a = system.arch(config)
    assert costs.param_count(config) == M.param_count(a)
    assert costs.active_param_count(config) == M.active_param_count(a)


def test_costs_at_deepseek_v2_lite_widths():
    config = spec.load_json(DSV2)
    assert costs.param_count(config) == pytest.approx(15.7e9, rel=0.01)
    # 27 latent-attention layers of 16 heads, d_qk 128 + 64, d_v 128
    assert costs.train_flops_per_token(config, 4096) == pytest.approx(
        6 * costs.active_param_count(config) + 3 * 27 * 16 * 320 * 4096)


def test_a_share_of_the_experts_counts_its_share_of_the_work():
    """8 of 64 experts held: a token's routed work here is 6 x 8 / 64
    experts, and the router keeps its 64 outputs."""
    whole = spec.load_json(DSV2)
    share = json.loads(json.dumps(whole))
    share["model"]["n_routed_experts"] = 8
    share["published"] = {"n_routed_experts": 64}
    per = 3 * 2048 * 1408
    assert costs.param_count(whole) - costs.param_count(share) == \
        26 * 56 * per
    assert costs.active_param_count(whole) - \
        costs.active_param_count(share) == pytest.approx(26 * (6 - 0.75) * per)


def test_new_architecture_cell_is_only_new_files(tmp_path):
    """A latent-attention, routed-expert cell is a configuration, a mix and
    a metric file declaring a scope of its own, plus new entries; the
    harness builds, counts, scopes and trains it with no edit of its
    code."""
    from bench.harness import scopes

    root = tiny.make_root(tmp_path)
    assert (root / "bench/configs/tiny-mla-moe.json").is_file()
    mix = json.loads((root / "bench/mixes/tiny-train.json").read_text())
    (root / "bench/mixes/tiny-moe.json").write_text(json.dumps(mix))
    (root / "bench/metrics/router_ms.moe.py").write_text(
        "from bench.harness import scopes\n\n"
        "SCOPE = ('router', 'mlp')\n\n\n"
        "def read(ctx):\n"
        "    return scopes.per_step(ctx, 'router')\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-mla-moe", "source": "test",
                             "file": "bench/configs/tiny-mla-moe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.moe", "config": "tiny-mla-moe",
                               "traffic": "tiny-moe", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][0]["workloads"].append("tiny.moe")
    bench["per_layer"][0]["workloads"].append("tiny.moe")
    bench["per_layer"].append({
        "name": "router_ms.moe", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "router",
        "moves": "train_tokens_per_s", "workloads": ["tiny.moe"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("tiny.moe", root=root)
    assert [m["name"] for m in cell.per_layer] == ["mfu.train",
                                                   "router_ms.moe"]
    readers = [spec.metric_reader(m["name"], root) for m in cell.per_layer]
    assert scopes.declared(readers) == {"router": "mlp"}
    assert costs.train_flops_per_token(cell.config, mix["seq_len"]) > 0
    job = train.Job(cell.config, cell.mix, 4294967301)
    out = job.first_steps()
    assert out["phase2"] == [0, 0, 1]
    assert all(np.isfinite(out["losses"]))
    for name in ("body/sb_0/moe/w_up_e", "body/sb_0/attn/w_ukv",
                 "head_0/mlp/w_gate"):
        assert out["grad_norms"][name] > 0
        assert np.linalg.norm(out["change"][name]) > 0
