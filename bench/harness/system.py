"""The system under test, built from a configuration file.

This is the only module of the benchmark that builds the program
(``repro``): its model and STEP recipe, as ``launch/train.py`` builds
them; ``harness/train.py`` drives the program's ``Trainer`` over them.

A configuration file's ``model`` block names the model by the published
``config.json`` keys, and ``arch`` translates each of them:

- the widths and counts (``num_hidden_layers``, ``hidden_size``, ...), the
  activation (``hidden_act``: ``gelu_new`` / ``gelu_pytorch_tanh`` are the
  program's tanh GELU MLP, ``silu`` its SwiGLU MLP), a ``norm`` of
  ``layernorm`` (also when absent) or ``rmsnorm`` with its epsilon,
  ``attention_bias``, ``sliding_window``, ``tie_word_embeddings``, ``dtype``;
- latent attention (``kv_lora_rank``, ``qk_nope_head_dim``,
  ``qk_rope_head_dim``, ``v_head_dim``) as the program's ``MLAConfig``;
- routed experts (``n_routed_experts``, ``num_experts_per_tok``,
  ``moe_intermediate_size``, ``n_shared_experts``,
  ``first_k_dense_replace`` of 0 or 1, ``moe_layer_freq`` of 1) as its
  ``MoEConfig``, the family then ``moe``.

Nothing is dropped silently.  A key the builder does not know, or a value
the program does not compute as published (``mlp_bias: true``, an epsilon
other than the program's, ``rope_scaling``, a low-rank query, the exact
erf ``gelu``, unrenormalised top-k gates), raises ``ValueError`` naming
the key, unless the file lists the key under ``assumed`` with its reason:
the program's own computation then stands.  A layer layout the program
cannot build raises even so.  The optional ``program`` block sets
``ArchConfig`` fields that no published key names (nested dicts for
``moe`` and ``mla``), applied last.
"""
from __future__ import annotations

import dataclasses

import jax

from bench.harness import weights as W

ACT = {"gelu_new": "gelu", "gelu_pytorch_tanh": "gelu", "silu": "swiglu"}
# the configuration's norm -> the program's, and the epsilon it computes with
NORM = {"layernorm": ("ln", 1e-5), "rmsnorm": ("rms", 1e-6)}
EPS_KEYS = ("norm_epsilon", "layer_norm_epsilon", "layer_norm_eps",
            "rms_norm_eps")
# the program's LayerNorm multiplies by its scale, its RMSNorm by 1 + scale
NORM_CENTRE = {"ln": 1.0, "rms": 0.0}
# keys whose one value the program computes
FIXED = {"mlp_bias": False, "rope_scaling": None, "position_embedding": "rope",
         "q_lora_rank": None, "norm_topk_prob": True, "seq_aux": False,
         "scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
         "topk_group": 1, "routed_scaling_factor": 1}
INFO = ("max_position_embeddings", "model_type")  # nothing is built from them
MLA_KEYS = ("kv_lora_rank", "qk_rope_head_dim", "qk_nope_head_dim",
            "v_head_dim")
MOE_KEYS = ("n_routed_experts", "num_experts_per_tok",
            "moe_intermediate_size", "n_shared_experts",
            "first_k_dense_replace", "moe_layer_freq")


def arch(config: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig
    from repro.models.mla import MLAConfig
    from repro.models.moe import MoEConfig

    m = dict(config["model"])
    departs: list[str] = []

    def need(key):
        if key not in m:
            raise ValueError(f"{config['name']}: model key {key!r} is missing")
        return m.pop(key)

    act = need("hidden_act")
    if act == "gelu":
        departs.append("hidden_act")  # exact erf GELU; the program's is tanh
    elif act not in ACT:
        raise ValueError(f"{config['name']}: hidden_act {act!r} is none of "
                         f"{sorted(ACT)}")
    norm = m.pop("norm", "layernorm")
    if norm not in NORM:
        raise ValueError(f"{config['name']}: norm {norm!r} is none of "
                         f"{sorted(NORM)}")
    norm, eps = NORM[norm]
    for key in EPS_KEYS:
        if key in m and float(m.pop(key)) != eps:
            departs.append(key)
    for key, value in FIXED.items():
        if key in m and m.pop(key) != value:
            departs.append(key)
    for key in INFO:
        m.pop(key, None)

    mla = moe = None
    if any(k in m for k in MLA_KEYS):
        r, rd, nd, vd = (need(k) for k in MLA_KEYS)
        mla = MLAConfig(kv_lora=r, rope_head_dim=rd, nope_head_dim=nd,
                        v_head_dim=vd)
    if any(k in m for k in MOE_KEYS):
        e, k, f, shared = (need(key) for key in MOE_KEYS[:4])
        dense = m.pop("first_k_dense_replace", 0)
        if dense not in (0, 1):
            raise ValueError(f"{config['name']}: first_k_dense_replace "
                             f"{dense!r}: the program builds 0 or 1")
        if m.pop("moe_layer_freq", 1) != 1:
            raise ValueError(f"{config['name']}: moe_layer_freq: the program "
                             "puts experts in every layer after the dense one")
        moe = MoEConfig(n_experts=e, top_k=k, d_ff_expert=f, n_shared=shared,
                        first_layer_dense=bool(dense))
    heads = need("num_attention_heads")
    bias = m.pop("attention_bias", False)
    window = m.pop("sliding_window", None)
    if mla is not None:  # the program's latent attention has neither
        if bias:
            departs.append("attention_bias")
        if window is not None:
            departs.append("sliding_window")
        bias, window = False, None
    a = ArchConfig(
        name=config["name"],
        family="dense" if moe is None else "moe",
        n_layers=need("num_hidden_layers"),
        d_model=need("hidden_size"),
        n_heads=heads,
        n_kv=m.pop("num_key_value_heads", heads),
        d_ff=need("intermediate_size"),
        vocab=need("vocab_size"),
        head_dim=m.pop("head_dim", None),
        qkv_bias=bias,
        o_bias=bias,
        mlp=ACT.get(act, "gelu"),
        norm=norm,
        rope="rope",
        rope_theta=float(need("rope_theta")),
        local_window=window,
        tie_embeddings=need("tie_word_embeddings"),
        moe=moe,
        mla=mla,
        param_dtype=need("dtype"),
        source=config["source"],
    )
    departs += list(m)  # keys the builder does not know
    bad = [k for k in departs if k not in config.get("assumed", {})]
    if bad:
        raise ValueError(
            f"{config['name']}: the program does not compute model keys "
            f"{bad} as the file states them; list each under 'assumed' with "
            "the reason, or state what the program computes")
    return _program(a, config.get("program", {}))


def _program(a, block: dict):
    """``a`` with the ``program`` block's fields set."""
    from repro.models.mla import MLAConfig
    from repro.models.moe import MoEConfig

    kw = dict(block)
    try:
        for key, cls in (("moe", MoEConfig), ("mla", MLAConfig)):
            if key in kw:
                cur = getattr(a, key)
                kw[key] = (dataclasses.replace(cur, **kw[key]) if cur
                           else cls(**kw[key]))
        return dataclasses.replace(a, **kw)
    except TypeError as e:
        raise ValueError(f"{a.name}: program block {block!r}: {e}") from None


def model(config: dict):
    from repro.models.model import TransformerLM

    return TransformerLM(arch(config))


def recipe(config: dict):
    import repro.core as core

    sp = config["sparsity"]
    return core.make_recipe(
        "step", core.SparsityConfig(default=core.NMSparsity(sp["n"], sp["m"]))
    )


def dense_params(mdl, key):
    """The dense tree, made on the device in one jitted call."""
    shapes = jax.eval_shape(mdl.init, jax.random.PRNGKey(0))
    centre = NORM_CENTRE[mdl.cfg.norm]
    return jax.jit(lambda k: W.make_tree(shapes, k, centre))(key)
