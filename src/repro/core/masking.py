"""N:M structured-sparsity mask math (pure jnp reference path).

An N:M mask keeps the N largest-magnitude elements out of every contiguous
group of M elements along a chosen axis of a weight tensor. For a matmul
weight stored ``(in_features, out_features)`` (the layout used throughout
``repro.models``: ``y = x @ W``), groups run along the *reduction* axis
(axis 0) so that an N:M-compressed matmul can skip pruned input channels —
the same convention NVIDIA ASP uses for Sparse Tensor Cores, and the one our
``kernels/nm_spmm`` Pallas kernel consumes.

:func:`nm_mask` ranks each element inside its group by elementwise compares
against the other ``M - 1`` (:func:`_group_rank`), on the weight with the
group axis split in place: one fused pass, with no sort, scatter or
transpose, for any number of leading or trailing axes (the layer-stacked
``(L, in, out)`` leaves the training step masks every step).

The Pallas-fused version of :func:`nm_mask` lives in ``repro.kernels.nm_mask``;
this module is the oracle (``kernels/ref.py`` re-exports from here) and the
default on CPU.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class NMSparsity:
    """An N:M sparsity pattern: keep ``n`` of every ``m`` consecutive elements.

    ``group_axis`` selects the tensor axis the groups run along (default 0,
    the reduction axis of an ``(in, out)`` matmul weight).
    """

    n: int
    m: int
    group_axis: int = 0

    def __post_init__(self):
        if not (1 <= self.n <= self.m):
            raise ValueError(f"need 1 <= N <= M, got {self.n}:{self.m}")

    @property
    def density(self) -> float:
        return self.n / self.m

    def __str__(self) -> str:  # "2:4"
        return f"{self.n}:{self.m}"


def _move_group_axis_last(w: jnp.ndarray, axis: int) -> jnp.ndarray:
    return jnp.moveaxis(w, axis, -1)


@partial(jax.jit, static_argnums=(1, 2))
def _group_rank(w: jnp.ndarray, m: int, group_axis: int) -> jnp.ndarray:
    """How many entries of its group of ``m`` along ``group_axis`` beat each
    entry of ``w``: a larger ``|w|``, or an equal one at a lower index.

    The axis is split in place to ``(..., G, m, ...)``, so the other axes keep
    their layout, and ``m`` is static: the rank unrolls into ``m * (m - 1)``
    elementwise compares that fuse into one pass over ``w`` (no sort, no
    scatter, no transpose). ``rank < n`` is ``top_k``'s choice of ``n``.
    """
    axis = group_axis % w.ndim
    if w.shape[axis] % m != 0:
        raise ValueError(
            f"axis {axis} of shape {w.shape} not divisible by group size {m}"
        )
    gshape = w.shape[:axis] + (w.shape[axis] // m, m) + w.shape[axis + 1:]
    a = jnp.abs(w).reshape(gshape)
    col = [jax.lax.index_in_dim(a, i, axis + 1, keepdims=False) for i in range(m)]

    def beats(j, i):  # entry j of a group ranks above entry i
        return (col[j] >= col[i]) if j < i else (col[j] > col[i])

    zero = jnp.zeros(col[0].shape, jnp.int32)
    rank = [
        sum((beats(j, i).astype(jnp.int32) for j in range(m) if j != i), zero)
        for i in range(m)
    ]
    return jnp.stack(rank, axis=axis + 1).reshape(w.shape)


def nm_mask(
    w: jnp.ndarray,
    n: int,
    m: int,
    group_axis: int = 0,
) -> jnp.ndarray:
    """Compute the binary N:M mask of ``w`` by magnitude.

    Returns a mask of ``w.dtype`` with exactly ``n`` ones per group of ``m``
    consecutive elements along ``group_axis``: an element is kept when fewer
    than ``n`` of its group beat it (:func:`_group_rank`). Ties are broken
    towards the lower index (deterministic), matching ``jax.lax.top_k``
    semantics.
    """
    if n == m:
        return jnp.ones_like(w)
    return (_group_rank(w, m, group_axis) < n).astype(w.dtype)


def nm_mask_and_apply(
    w: jnp.ndarray, n: int, m: int, group_axis: int = 0
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Return ``(mask, mask * w)`` — the fused form the Pallas kernel mirrors."""
    mask = nm_mask(w, n, m, group_axis)
    return mask, mask * w


def nm_compress(
    w: jnp.ndarray, n: int, m: int, group_axis: int = 0
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Compress ``w`` to its N:M representation.

    Returns ``(values, indices)`` where along ``group_axis`` only the kept
    elements remain: ``values`` has size ``dim * n / m`` on that axis, and
    ``indices`` (uint8, same shape as values) holds each kept element's
    offset within its group of ``m``. Indices within a group are sorted
    ascending so decompression is order-stable.
    """
    axis = group_axis % w.ndim
    wt = _move_group_axis_last(w, axis)
    gshape = wt.shape[:-1] + (wt.shape[-1] // m, m)
    groups = wt.reshape(gshape)
    _, idx = jax.lax.top_k(jnp.abs(groups), n)  # (..., G, n)
    idx = jnp.sort(idx, axis=-1)
    vals = jnp.take_along_axis(groups, idx, axis=-1)  # (..., G, n)
    out_shape = wt.shape[:-1] + (gshape[-2] * n,)
    vals = vals.reshape(out_shape)
    idx = idx.astype(jnp.uint8).reshape(out_shape)
    return jnp.moveaxis(vals, -1, axis), jnp.moveaxis(idx, -1, axis)


def nm_decompress(
    values: jnp.ndarray,
    indices: jnp.ndarray,
    n: int,
    m: int,
    group_axis: int = 0,
) -> jnp.ndarray:
    """Scatter an (values, indices) N:M-compressed tensor back to dense."""
    axis = group_axis % values.ndim
    vt = _move_group_axis_last(values, axis)
    it = _move_group_axis_last(indices, axis).astype(jnp.int32)
    g = vt.shape[-1] // n
    vt = vt.reshape(vt.shape[:-1] + (g, n))
    it = it.reshape(it.shape[:-1] + (g, n))
    dense = jnp.zeros(vt.shape[:-1] + (m,), dtype=values.dtype)
    dense = jnp.put_along_axis(dense, it, vt, axis=-1, inplace=False)
    dense = dense.reshape(dense.shape[:-2] + (g * m,))
    return jnp.moveaxis(dense, -1, axis)


def nm_mask_dynamic(
    w: jnp.ndarray,
    n: jnp.ndarray,
    m: int,
    group_axis: int = 0,
) -> jnp.ndarray:
    """N:M mask where N is a *traced* scalar (needed by the Decaying-Mask
    recipe, whose N shrinks over training inside a jitted step).

    The same group rank as :func:`nm_mask`, compared with the traced ``n``:
    ``mask[i] = rank(|w[i]|) < n``.
    """
    return (_group_rank(w, m, group_axis) < n).astype(w.dtype)


def sparsity_fraction(mask: jnp.ndarray) -> jnp.ndarray:
    """Fraction of zeros in a mask (1 - density)."""
    return 1.0 - jnp.mean(mask.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Straight-Through Estimator primitives (paper Eq. 8 / Eq. 9).
# ---------------------------------------------------------------------------


@jax.custom_vjp
def straight_through_mask(w: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """``mask * w`` in the forward pass; identity gradient to ``w`` (STE).

    This is Eq. (8) of the paper: the loss is evaluated at ``Π ⊙ w`` but the
    full gradient is applied to the dense ``w`` (d(Π⊙w)/dw ≈ I), which is what
    lets pruned weights regrow and the mask keep evolving.
    """
    return w * mask


def _stm_fwd(w, mask):
    return w * mask, None


def _stm_bwd(_, g):
    return (g, None)


straight_through_mask.defvjp(_stm_fwd, _stm_bwd)


def masked_no_ste(w: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """``mask * w`` with the *true* gradient ``mask * g`` (no straight-through).

    Used by the ASP recipe, where the mask is fixed and pruned weights must
    stay dead.
    """
    return w * jax.lax.stop_gradient(mask)


def sr_ste_grad_term(
    w: jnp.ndarray, mask: jnp.ndarray, lam: float
) -> jnp.ndarray:
    """The SR-STE regularization term ``λ (1 − Π) ⊙ w`` (paper Eq. 9).

    Added to the STE gradient; decays pruned weights towards zero so the mask
    stabilizes.
    """
    return lam * (1.0 - mask) * w
