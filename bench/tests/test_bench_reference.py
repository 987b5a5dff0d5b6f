"""The plain reference against the program at a small size on the CPU:
the seeded weights, and the STEP steps (two precondition steps, then one
mask-learning step)."""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import spec, system, train
from bench.harness import weights as W
from bench.tests import tiny

CFG = spec.load_json(tiny.DATA / "configs" / "tiny-2of4.json")
MIX = spec.load_json(tiny.DATA / "mixes" / "tiny-train.json")


def test_weights_are_the_same_made_whole_or_layer_by_layer():
    key = W.seed_key(11)
    whole = W.leaf(key, "body/sb_0/mlp/w_fc", (3, 16, 8), jnp.bfloat16, True)
    for l in range(3):
        one = W.leaf(key, "body/sb_0/mlp/w_fc", (16, 8), jnp.bfloat16, True,
                     layer=l)
        np.testing.assert_array_equal(whole[l], one)
    assert float(jnp.std(whole.astype(jnp.float32))) == pytest.approx(
        (2 / 24) ** 0.5, rel=0.3)


def test_step_train_steps_match_the_reference():
    job = train.Job(CFG, MIX, 5)
    prog = job.first_steps()
    assert prog["phase2"] == [0, 0, 1]
    ref = spec.reference(CFG).train_steps(CFG, 5, job.checked_batches(),
                                          train.opt_args(MIX), rows_per_block=2)
    g = train.compare(prog, ref, CFG["limits"]["train_change_trim"])
    assert g["loss"] < 0.01 and g["grad"] < 0.01 and g["change"] < 0.05
    assert g["left_out"] == []  # the key bias is rotated with its key
    assert ref["phase2"] == prog["phase2"]


def test_reference_stores_each_leaf_as_the_program_does():
    mdl = system.model(CFG)
    shapes = jax.eval_shape(mdl.init, jax.random.PRNGKey(0))
    prog = {W.path_str(p): s.dtype
            for p, s in jax.tree_util.tree_leaves_with_path(shapes)}
    assert spec.reference(CFG).stored(CFG) == prog
    dense = system.dense_params(mdl, W.seed_key(9))
    ref = spec.reference(CFG).all_weights(CFG, 9)
    for p, x in jax.tree_util.tree_leaves_with_path(dense):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      ref[W.path_str(p)])


def _crc(tree) -> int:
    c = 0
    for p, x in sorted(jax.tree_util.tree_leaves_with_path(tree),
                       key=lambda t: W.path_str(t[0])):
        c = zlib.crc32(np.asarray(x).tobytes(), c)
    return c


def test_the_layernorm_tree_is_the_bits_it_was():
    # the CRC of the test configuration's tree as the generator made it
    # before the norm scale's centre came from the builder
    mdl = system.model(CFG)
    assert _crc(system.dense_params(mdl, W.seed_key(4294967301))) == 712933969


def test_expert_model_weights_are_the_same_made_whole_or_layer_by_layer():
    """The latent-attention, routed-expert tree: the scan-stacked body
    (``(L, E, in, out)`` expert stacks among it) drawn layer by layer,
    the dense first layer ``head_0`` unstacked, and RMSNorm scales around
    0, as the program's ``1 + scale`` wants them."""
    cfg = spec.load_json(tiny.DATA / "configs" / "tiny-mla-moe.json")
    mdl = system.model(cfg)
    key = W.seed_key(4294967301)
    whole = system.dense_params(mdl, key)
    leaves = {W.path_str(p): x
              for p, x in jax.tree_util.tree_leaves_with_path(whole)}
    assert leaves["body/sb_0/moe/w_gate_e"].shape == (2, 8, 64, 32)
    assert "head_0/mlp/w_gate" in leaves
    for path, x in leaves.items():
        if path.startswith("body/"):
            for l in range(x.shape[0]):
                one = W.leaf(key, path, x.shape[1:], x.dtype, True, layer=l,
                             centre=0.0)
                np.testing.assert_array_equal(x[l], one)
        else:
            np.testing.assert_array_equal(
                x, W.leaf(key, path, x.shape, x.dtype, False, centre=0.0))
        if path.endswith("norm_scale"):
            assert float(jnp.max(jnp.abs(x))) <= 0.02 * 3 ** 0.5
