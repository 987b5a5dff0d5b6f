"""Unit + property tests for the N:M masking math (paper Eq. 8/9 substrate).

hypothesis is an optional dependency: without it the fixed-case tests still
run and the property sweeps are skipped.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import given, settings, st  # hypothesis, or a skip shim

from repro.core import masking as mk

jax.config.update("jax_platform_name", "cpu")


NM_CASES = [(1, 4), (2, 4), (3, 4), (1, 8), (2, 8), (4, 8), (1, 16), (4, 16), (8, 32)]


@pytest.mark.parametrize("n,m", NM_CASES)
@pytest.mark.parametrize("axis", [0, 1])
def test_mask_exact_n_per_group(n, m, axis):
    w = jax.random.normal(jax.random.PRNGKey(0), (m * 3, m * 2))
    mask = mk.nm_mask(w, n, m, axis)
    wt = jnp.moveaxis(mask, axis, -1)
    groups = wt.reshape(wt.shape[0], -1, m)
    counts = groups.sum(-1)
    assert (counts == n).all(), counts


@pytest.mark.parametrize("n,m", NM_CASES)
def test_mask_keeps_largest(n, m):
    w = jax.random.normal(jax.random.PRNGKey(1), (m * 4, 8))
    mask = mk.nm_mask(w, n, m, 0)
    aw = jnp.abs(w)
    groups = jnp.moveaxis(aw, 0, -1).reshape(8, -1, m)
    gm = jnp.moveaxis(mask, 0, -1).reshape(8, -1, m)
    kept_min = jnp.where(gm > 0, groups, jnp.inf).min(-1)
    dropped_max = jnp.where(gm == 0, groups, -jnp.inf).max(-1)
    assert (kept_min >= dropped_max).all()


def test_mask_n_equals_m_is_dense():
    w = jax.random.normal(jax.random.PRNGKey(2), (8, 8))
    assert (mk.nm_mask(w, 4, 4, 0) == 1).all()


def test_mask_indivisible_raises():
    w = jnp.zeros((10, 8))
    with pytest.raises(ValueError):
        mk.nm_mask(w, 2, 4, 0)


def _topk_mask(w, n, m, axis):
    """The N:M mask by ``top_k`` and a ``put_along_axis`` scatter: the oracle
    that ``nm_mask``'s in-group comparisons must match bit for bit."""
    wt = jnp.moveaxis(w, axis, -1)
    groups = jnp.abs(wt.reshape(wt.shape[:-1] + (wt.shape[-1] // m, m)))
    _, idx = jax.lax.top_k(groups, n)
    ones = jnp.ones(idx.shape, w.dtype)
    mask = jnp.put_along_axis(jnp.zeros_like(groups), idx, ones, axis=-1, inplace=False)
    return jnp.moveaxis(mask.reshape(wt.shape), -1, axis)


ORACLE_VALUES = {
    "random": lambda w: w,
    "ties": lambda w: jnp.round(w),  # a handful of levels: most groups tie
    "zeros": jnp.zeros_like,
    "signed_zeros": lambda w: jnp.where(w > 0, 0.0, -0.0).astype(w.dtype),
}


@pytest.mark.parametrize("values", sorted(ORACLE_VALUES))
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("layout", ["2d_axis0", "2d_axis1", "stacked_axis-2"])
@pytest.mark.parametrize("n,m", [(1, 4), (2, 4), (2, 8), (4, 8)])
def test_mask_matches_topk_oracle(n, m, layout, dtype, values):
    shape, axis = {
        "2d_axis0": ((6 * m, 40), 0),
        "2d_axis1": ((40, 6 * m), 1),
        "stacked_axis-2": ((3, 4 * m, 40), -2),
    }[layout]
    w = ORACLE_VALUES[values](
        jax.random.normal(jax.random.PRNGKey(n * 10 + m), shape).astype(dtype)
    )
    mask = mk.nm_mask(w, n, m, axis)
    oracle = _topk_mask(w, n, m, axis % w.ndim)
    assert mask.dtype == w.dtype
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(oracle))


def _hlo_ops(fn, w):
    text = jax.jit(fn).lower(w).compile().as_text()
    return set(re.findall(r"\s(sort|scatter)\(", text))


def test_mask_compiles_without_sort_or_scatter():
    """The stacked-leaf mask is elementwise compares: no sort, no scatter
    (the oracle's HLO shows the pattern finds both when they are there)."""
    w = jax.ShapeDtypeStruct((2, 64, 256), jnp.bfloat16)
    assert _hlo_ops(lambda w: mk.nm_mask(w, 2, 4, -2), w) == set()
    assert _hlo_ops(lambda w: _topk_mask(w, 2, 4, 1), w) == {"sort", "scatter"}


@settings(max_examples=25, deadline=None)
@given(
    st.sampled_from(NM_CASES),
    st.integers(1, 4),
    st.integers(1, 3),
    st.integers(0, 2**31 - 1),
)
def test_compress_roundtrip_property(nm, g, cols, seed):
    n, m = nm
    w = jax.random.normal(jax.random.PRNGKey(seed), (g * m, cols * 4))
    mask = mk.nm_mask(w, n, m, 0)
    v, i = mk.nm_compress(w, n, m, 0)
    assert v.shape == (g * n, cols * 4)
    assert i.dtype == jnp.uint8
    dense = mk.nm_decompress(v, i, n, m, 0)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(mask * w), rtol=0, atol=0)


@settings(max_examples=20, deadline=None)
@given(st.sampled_from([(1, 4), (2, 4), (2, 8)]), st.integers(0, 2**31 - 1))
def test_dynamic_matches_static(nm, seed):
    n, m = nm
    w = jax.random.normal(jax.random.PRNGKey(seed), (m * 5, 12))
    static = mk.nm_mask(w, n, m, 0)
    dynamic = mk.nm_mask_dynamic(w, jnp.asarray(n), m, 0)
    np.testing.assert_array_equal(np.asarray(static), np.asarray(dynamic))


def test_straight_through_gradient_is_identity():
    w = jax.random.normal(jax.random.PRNGKey(3), (8, 4))
    mask = mk.nm_mask(w, 2, 4, 0)
    # f = sum(sin(masked_w)); STE grad must be cos evaluated at masked point,
    # WITHOUT the mask factor (pruned coords still receive gradient)
    g = jax.grad(lambda w: jnp.sum(jnp.sin(mk.straight_through_mask(w, mask))))(w)
    expected = jnp.cos(w * mask)
    np.testing.assert_allclose(np.asarray(g), np.asarray(expected), rtol=1e-6)


def test_masked_no_ste_kills_pruned_grads():
    w = jax.random.normal(jax.random.PRNGKey(4), (8, 4))
    mask = mk.nm_mask(w, 2, 4, 0)
    g = jax.grad(lambda w: jnp.sum(jnp.sin(mk.masked_no_ste(w, mask))))(w)
    assert (np.asarray(g)[np.asarray(mask) == 0] == 0).all()


def test_sr_ste_term_only_on_pruned():
    w = jax.random.normal(jax.random.PRNGKey(5), (8, 4))
    mask = mk.nm_mask(w, 2, 4, 0)
    term = mk.sr_ste_grad_term(w, mask, 0.5)
    np.testing.assert_allclose(np.asarray(term), np.asarray(0.5 * (1 - mask) * w))


def test_3d_weights_supported():
    # MoE expert stacks (E, d, f) with groups along d (axis 1)
    w = jax.random.normal(jax.random.PRNGKey(6), (4, 16, 8))
    mask = mk.nm_mask(w, 2, 4, 1)
    groups = jnp.moveaxis(mask, 1, -1).reshape(4, 8, 4, 4).sum(-1)
    assert (groups == 2).all()
