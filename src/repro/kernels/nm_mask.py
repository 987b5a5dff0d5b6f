"""Pallas TPU kernel: fused N:M mask computation + application.

The per-step hot-spot of every STE-family recipe is re-deriving the N:M mask
of every weight tensor from its current magnitudes (paper Eq. 8: Π_t is
recomputed from w_t each step). The pure-jnp path (in-group rank by
elementwise compares, ``core.masking.nm_mask``) is one fused XLA pass over
the weight of any rank; this kernel streams each 2-D weight tile through
VMEM exactly once and emits (Π⊙w, Π) with no extra HBM round-trips.

Algorithm (inside one (TR, TC) VMEM block, groups of M running down rows —
axis 0 is the matmul reduction axis, matching ``core.masking``):
reshape to (G, M, TC); then N rounds of iterative argmax per (group, col):
mark the largest unselected |w|, deterministic lowest-index tie-break via a
row-iota argmin trick. N and M are compile-time constants, so the selection
loop fully unrolls into VPU ops — no sort network, no gather.

Block shapes: TR=256 rows (any multiple of M), TC=256 lanes (multiple of the
128-lane VREG). VMEM footprint/block: in + 2 outs + f32 scratch ≈
256·256·(2+2+2+4)B ≈ 640 KiB — comfortably inside the ~16 MiB/core budget,
leaving room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import dispatch


def _nm_mask_kernel(w_ref, masked_ref, mask_ref, *, n: int, m: int):
    w = w_ref[...]  # (TR, TC)
    tr, tc = w.shape
    g = tr // m
    aw = jnp.abs(w.astype(jnp.float32)).reshape(g, m, tc)
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (g, m, tc), 1)
    selected = jnp.zeros((g, m, tc), jnp.bool_)
    for _ in range(n):  # unrolled: n is static
        cand = jnp.where(selected, -jnp.inf, aw)
        mx = jnp.max(cand, axis=1, keepdims=True)  # (G,1,TC)
        is_max = cand == mx
        # deterministic tie-break: lowest row index among the maxima
        pick = jnp.min(jnp.where(is_max, row_iota, m), axis=1, keepdims=True)
        selected = selected | (row_iota == pick)
    mask = selected.reshape(tr, tc)
    mask_ref[...] = mask.astype(w_ref.dtype)
    masked_ref[...] = jnp.where(mask, w, jnp.zeros_like(w))


@functools.partial(jax.jit, static_argnames=("n", "m", "block_r", "block_c", "interpret"))
def nm_mask_apply_pallas(
    w: jnp.ndarray,
    n: int,
    m: int,
    block_r: int = 256,
    block_c: int = 256,
    *,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused (Π⊙w, Π) for a 2-D weight ``w`` with groups along axis 0.

    ``interpret=True`` runs the kernel body under the Pallas interpreter
    (any backend, correctness only); ``interpret=False`` compiles it for
    the TPU.  No default: callers say which one they mean (the
    ``kernels.dispatch`` registry picks per backend).
    """
    assert w.ndim == 2, "kernel operates on 2-D matmul weights"
    r, c = w.shape
    assert r % m == 0, (r, m)
    br = min(block_r, r)
    br -= br % m or 0
    bc = min(block_c, c)
    # pad to block multiples (pallas grids need exact tiling)
    rp = -(-r // br) * br
    cp = -(-c // bc) * bc
    wp = jnp.pad(w, ((0, rp - r), (0, cp - c)))
    grid = (rp // br, cp // bc)
    masked, mask = pl.pallas_call(
        functools.partial(_nm_mask_kernel, n=n, m=m),
        grid=grid,
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, cp), w.dtype),
            jax.ShapeDtypeStruct((rp, cp), w.dtype),
        ],
        interpret=interpret,
    )(wp)
    return masked[:r, :c], mask[:r, :c]


# ---------------------------------------------------------------------------
# dispatch registration: nm_mask routes through the kernels.dispatch
# registry like nm_spmm / paged_attn (the legacy prefer_pallas/interpret
# knobs in kernels.ops are retired).  All three modes return (Π, Π⊙w).
# ---------------------------------------------------------------------------


def _kernel_entry(w, n: int, m: int, *, interpret: bool):
    masked, mask = nm_mask_apply_pallas(w, n, m, interpret=interpret)
    return mask, masked


def _xla_entry(w, n: int, m: int):
    from repro.core import masking as ref_masking

    mask = ref_masking.nm_mask(w, n, m, 0)
    return mask, mask * w


dispatch.register(
    "nm_mask", "pallas", functools.partial(_kernel_entry, interpret=False)
)
dispatch.register(
    "nm_mask", "interpret", functools.partial(_kernel_entry, interpret=True)
)
dispatch.register("nm_mask", "xla", _xla_entry)
# shape gating (2-D, whole N:M groups down the rows) lives in
# dispatch.nm_mask itself: it must override forced/env modes too, which a
# resolve()-level guard cannot, so keeping a guard here would just be a
# second stale copy of the same predicate
