"""The system under test, built from a configuration file.

This is the only module of the benchmark that builds the program
(``repro``): its model and STEP recipe, as ``launch/train.py`` builds
them; ``harness/train.py`` drives the program's ``Trainer`` over them.
"""
from __future__ import annotations

import jax

from bench.harness import weights as W


def arch(config: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig

    m = config["model"]
    return ArchConfig(
        name=config["name"],
        family="dense",
        n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv=m["num_key_value_heads"],
        d_ff=m["intermediate_size"],
        vocab=m["vocab_size"],
        head_dim=m["head_dim"],
        qkv_bias=m["attention_bias"],
        o_bias=m["attention_bias"],
        mlp="gelu",
        norm="ln",
        rope="rope",
        rope_theta=float(m["rope_theta"]),
        local_window=m["sliding_window"],
        tie_embeddings=m["tie_word_embeddings"],
        param_dtype=m["dtype"],
        source=config["source"],
    )


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
    return jax.jit(lambda k: W.make_tree(shapes, k))(key)
