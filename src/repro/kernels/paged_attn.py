"""Pallas paged decode-attention: the kernel walks the page table directly.

The PR-2 paged KV pool stored pages device-side but the decode step still
*gathered* every lane's logical ``(B, S_max, ...)`` view contiguous before
``layers.decode_attention`` — per step, per layer, the full logical cache
was rewritten through HBM.  This kernel consumes the pool and the page
table as-is: the grid's innermost dimension walks one lane's table slots,
each step's BlockSpec index map reads ``tables[b, p]`` (scalar-prefetched,
so the address is known before the body runs) and DMAs exactly that
physical page HBM→VMEM, and a flash-style online softmax accumulates across
pages in f32 VMEM scratch.  Sentinel (unmapped) slots clamp their DMA to a
resident page and skip all compute under ``pl.when``; sliding-window lanes
visit only slots whose logical page intersects the live window.

Bytes per decode step (the quantity this kernel exists to shrink; the
engine's stats report it as ``kv_bytes_per_step`` / ``bytes_read_per_step``):
the gathered path materializes every lane's full ``S_max`` logical view
per layer per step; the kernel reads each lane's ``ceil(len/ps)`` live
pages once.  Measured: the slab-vs-paged serve sweep averages ~24.3 KB of
live KV per step (up to 5 concurrent heterogeneous lanes) where the
gathered view is ~328 KB — a 13x byte gap — and at 12.5–25% occupancy
the kernel reads 0.156x–0.312x of the gathered bytes.  The gap widens linearly with ``S_max / len``.

Operand contract (kernel layout — callers reshape, see
``models.cache.PagedLayout.attn_decode`` / ``models.mla.mla_decode``):

    q         (B, Hkv, G, D)   queries grouped per KV head
    k_pages   (P, Hkv, ps, D)  physical pool (P = num_pages, sentinel = P)
    v_pages   (P, Hkv, ps, Dv) pool; pass ``v_is_k=True`` to reuse
                               ``k_pages`` (MLA: V *is* the latent)
    tables    (B, n_slots) int32 page table; slot value P means unmapped
    lengths   (B,)        int32 live tokens per lane (pos + 1)
    q2/k2_pages            optional second score stream, added into the
                           logits pre-softmax (MLA: the RoPE key part)
    window/win_slots       sliding-window width and modular table slots;
                           slot ``s`` holds logical page ``pg`` with
                           ``pg ≡ s (mod win_slots)``

Two shapes cover the zoo:

- **GQA**: ``G = H // Hkv``, ``D = Dv = head_dim``.
- **MLA-latent** (absorbed decode): ``Hkv = 1``, ``G = H``,
  ``D = kv_lora``, ``q2/k2`` carry the shared RoPE key, ``v_is_k=True``
  so the latent pool is streamed once and ``o = p @ c_kv`` comes back in
  latent space (the caller up-projects with the absorbed ``W_uv``).

``paged_attn_xla`` is the parity oracle: the same masking math on the
table-gathered view (it *does* materialize ``(B, n_slots·ps, ...)`` — that
is the point of reference, not a production route).  Accumulation order
differs (per-page flash vs one softmax), so parity is fp-tolerance, not
bit-level; see ``tests/test_paged_attn.py`` for the locked tolerances.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import dispatch

_NEG = -1e30  # finite -inf stand-in: keeps masked lanes exp()-safe


def _paged_attn_kernel(
    tables_ref,  # (B, n_slots) int32, scalar-prefetched
    lengths_ref,  # (B,) int32, scalar-prefetched
    *refs,
    page_size: int,
    window: int,
    win_slots: int,
    scale: float,
    sentinel: int,
    has_k2: bool,
    has_scale: bool,
    v_is_k: bool,
    emit_stats: bool,
):
    it = iter(refs)
    q_ref = next(it)
    q2_ref = next(it) if has_k2 else None
    k_ref = next(it)
    ks_ref = next(it) if has_scale else None
    k2_ref = next(it) if has_k2 else None
    k2s_ref = next(it) if (has_k2 and has_scale) else None
    v_ref = k_ref if v_is_k else next(it)
    vs_ref = None if v_is_k else (next(it) if has_scale else None)
    o_ref = next(it)
    m_ref = next(it) if emit_stats else None
    l_ref = next(it) if emit_stats else None
    m_scr, l_scr, acc_scr = it

    b, h, p = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[b]
    phys = tables_ref[b, p]
    ps = page_size
    if window:
        # modular table: slot p holds the newest logical page ≡ p (mod slots)
        cur_pg = jnp.maximum(length - 1, 0) // ps
        pg = cur_pg - jnp.mod(cur_pg - p, win_slots)
        lo = jnp.maximum(length - window, 0)
    else:
        pg = p
        lo = 0
    base = pg * ps
    live = (
        (phys != sentinel)
        & (length > 0)
        & (base < length)
        & (base + ps > lo)
    )
    if window:
        live &= pg >= 0  # slot not yet reached by this lane

    @pl.when(live)
    def _page():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (ps, D)
        if has_scale:
            # int8 pages: per-(page, slot) scales dequantize in VMEM, so
            # HBM only ever streams the 1-byte codes
            k = k * ks_ref[0]  # (ps, 1) f32
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (G, ps)
        if has_k2:
            q2 = q2_ref[0, 0].astype(jnp.float32)
            k2 = k2_ref[0, 0].astype(jnp.float32)
            if has_scale:
                k2 = k2 * k2s_ref[0]
            s = s + jax.lax.dot_general(
                q2, k2, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        s = s * scale
        apos = base + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        ok = (apos < length) & (apos >= lo)
        s = jnp.where(ok, s, _NEG)
        m_prev = m_scr[:, :1]  # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pexp = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_scr[:, :1] + jnp.sum(pexp, axis=-1, keepdims=True)
        if v_is_k:
            v = k  # (ps, Dv) — already dequantized above
        else:
            v = v_ref[0, 0].astype(jnp.float32)  # (ps, Dv)
            if has_scale:
                v = v * vs_ref[0]
        acc_scr[...] = corr * acc_scr[...] + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(p == pl.num_programs(2) - 1)
    def _flush():
        if emit_stats:
            # raw flash stats: the shard_map wrapper renormalizes across
            # shards (pmax the maxima, psum the corrected l and acc)
            o_ref[0, 0] = acc_scr[...].astype(o_ref.dtype)
            m_ref[0, 0] = m_scr[...].astype(m_ref.dtype)
            l_ref[0, 0] = l_scr[...].astype(l_ref.dtype)
        else:
            # dead lanes (l == 0) flush exact zeros, not NaNs
            o_ref[0, 0] = (
                acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
            ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "window", "win_slots", "v_is_k", "interpret", "emit_stats",
    ),
)
def paged_attn_pallas(
    q: jnp.ndarray,  # (B, Hkv, G, D)
    k_pages: jnp.ndarray,  # (P, Hkv, ps, D)
    v_pages: Optional[jnp.ndarray],  # (P, Hkv, ps, Dv) or None when v_is_k
    tables: jnp.ndarray,  # (B, n_slots) int32
    lengths: jnp.ndarray,  # (B,) int32
    *,
    scale: float,
    window: int = 0,
    win_slots: int = 0,
    q2: Optional[jnp.ndarray] = None,  # (B, Hkv, G, D2)
    k2_pages: Optional[jnp.ndarray] = None,  # (P, Hkv, ps, D2)
    k_scale: Optional[jnp.ndarray] = None,  # (P, ps) int8-page scales
    v_scale: Optional[jnp.ndarray] = None,  # (P, ps)
    k2_scale: Optional[jnp.ndarray] = None,  # (P, ps)
    v_is_k: bool = False,
    interpret: bool = False,
    emit_stats: bool = False,
) -> jnp.ndarray:
    """Fused paged decode attention; returns ``(B, Hkv, G, Dv)``.

    Grid ``(B, Hkv, n_slots)`` with the table slot innermost; page blocks
    are addressed through the scalar-prefetched table so only mapped pages
    move HBM→VMEM (consecutive sentinel slots clamp to the same resident
    page and re-use the previous DMA).

    int8 pools pass ``k_scale``/``v_scale`` (``k2_scale`` for the RoPE
    stream; ``v_is_k`` reuses ``k_scale``): per-(page, slot) scales (any
    fp dtype; upcast to f32) that ride the same table-addressed DMA and
    dequantize each page in VMEM before the dot — identical flash math, 1-byte HBM traffic.

    With ``emit_stats=True`` the normalization is skipped and the raw
    flash triple ``(acc, m, l)`` comes back in f32 — ``acc`` is the
    unnormalized ``(B, Hkv, G, Dv)`` accumulator, ``m``/``l`` the running
    max/denominator ``(B, Hkv, G)``.  The shard_map wrapper combines these
    across pool shards before dividing (``kernels.sharded.combine_stats``).
    """
    b, hkv, g, d = q.shape
    p_pages, ps = k_pages.shape[0], k_pages.shape[2]
    n_slots = tables.shape[1]
    has_k2 = q2 is not None
    has_scale = k_scale is not None
    dv = d if v_is_k else v_pages.shape[-1]

    def q_index(b_, h_, p_, tables_, lengths_):
        return (b_, h_, 0, 0)

    def page_index(b_, h_, p_, tables_, lengths_):
        return (jnp.minimum(tables_[b_, p_], p_pages - 1), h_, 0, 0)

    def scale_index(b_, h_, p_, tables_, lengths_):
        # scales have no head axis: one (ps, 1) column per page
        return (jnp.minimum(tables_[b_, p_], p_pages - 1), 0, 0)

    def scale_spec():
        return pl.BlockSpec((1, ps, 1), scale_index)

    def scale_op(s):
        return s.astype(jnp.float32).reshape(p_pages, ps, 1)

    in_specs = [pl.BlockSpec((1, 1, g, d), q_index)]
    operands = [q]
    if has_k2:
        in_specs.append(pl.BlockSpec((1, 1, g, q2.shape[-1]), q_index))
        operands.append(q2)
    in_specs.append(pl.BlockSpec((1, 1, ps, d), page_index))
    operands.append(k_pages)
    if has_scale:
        in_specs.append(scale_spec())
        operands.append(scale_op(k_scale))
    if has_k2:
        in_specs.append(pl.BlockSpec((1, 1, ps, k2_pages.shape[-1]), page_index))
        operands.append(k2_pages)
        if has_scale:
            in_specs.append(scale_spec())
            operands.append(scale_op(k2_scale))
    if not v_is_k:
        in_specs.append(pl.BlockSpec((1, 1, ps, dv), page_index))
        operands.append(v_pages)
        if has_scale:
            in_specs.append(scale_spec())
            operands.append(scale_op(v_scale))

    if emit_stats:
        # m/l leave as 128-wide lane-aligned blocks, sliced outside
        out_shape = [
            jax.ShapeDtypeStruct((b, hkv, g, dv), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g, 128), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g, 128), jnp.float32),
        ]
        out_specs = [
            pl.BlockSpec((1, 1, g, dv), q_index),
            pl.BlockSpec((1, 1, g, 128), q_index),
            pl.BlockSpec((1, 1, g, 128), q_index),
        ]
    else:
        out_shape = jax.ShapeDtypeStruct((b, hkv, g, dv), q.dtype)
        out_specs = pl.BlockSpec((1, 1, g, dv), q_index)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, n_slots),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),  # running max
            pltpu.VMEM((g, 128), jnp.float32),  # running denominator
            pltpu.VMEM((g, dv), jnp.float32),  # output accumulator
        ],
    )
    kernel = functools.partial(
        _paged_attn_kernel,
        page_size=ps,
        window=window,
        win_slots=win_slots,
        scale=scale,
        sentinel=p_pages,
        has_k2=has_k2,
        has_scale=has_scale,
        v_is_k=v_is_k,
        emit_stats=emit_stats,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), *operands)
    if emit_stats:
        acc, mm, ll = out
        return acc, mm[..., 0], ll[..., 0]
    return out


# ---------------------------------------------------------------------------
# XLA oracle: identical masking math on the table-gathered view
# ---------------------------------------------------------------------------


def _gathered_stats(
    q, k_pages, v_pages, tables, lengths, *,
    scale, window, win_slots, q2, k2_pages, v_is_k,
    k_scale=None, v_scale=None, k2_scale=None,
):
    """Gathered masking math in unnormalized-stats form: ``(acc, m, l)``
    f32 with ``acc = (B, Hkv, G, Dv)``, ``m``/``l`` ``(B, Hkv, G)``.
    Shared by the normalized oracle and the stats entry the shard_map
    wrapper's XLA inner route uses."""
    b, hkv, g, d = q.shape
    p_pages, ps = k_pages.shape[0], k_pages.shape[2]
    n_slots = tables.shape[1]
    lengths = lengths.reshape(b, 1).astype(jnp.int32)
    slot = jnp.arange(n_slots)[None, :]  # (1, S)
    if window:
        cur_pg = jnp.maximum(lengths - 1, 0) // ps
        pg = cur_pg - jnp.mod(cur_pg - slot, win_slots)
        lo = jnp.maximum(lengths - window, 0)
    else:
        pg = jnp.broadcast_to(slot, (b, n_slots))
        lo = jnp.zeros((b, 1), jnp.int32)
    base = pg * ps
    apos = base[..., None] + jnp.arange(ps)[None, None, :]  # (B, S, ps)
    valid = (
        (apos < lengths[..., None])
        & (apos >= lo[..., None])
        & (tables[..., None] != p_pages)
        & (pg[..., None] >= 0)
    )
    phys = jnp.minimum(tables, p_pages - 1)  # (B, S)

    def deq(pages, sc):
        g_ = pages[phys].astype(jnp.float32)  # (B, S, Hkv, ps, D) — the gather
        if sc is not None:
            g_ = g_ * sc[phys].astype(jnp.float32)[:, :, None, :, None]
        return g_

    kg = deq(k_pages, k_scale)
    s = jnp.einsum("bhgd,bshpd->bhgsp", q.astype(jnp.float32), kg)
    if q2 is not None:
        s = s + jnp.einsum(
            "bhgd,bshpd->bhgsp", q2.astype(jnp.float32), deq(k2_pages, k2_scale)
        )
    s = jnp.where(valid[:, None, None], s * scale, _NEG)
    m = jnp.max(s, axis=(-2, -1))  # (B, Hkv, G); _NEG on dead lanes
    pexp = jnp.exp(s - m[..., None, None]) * valid[:, None, None]
    l = jnp.sum(pexp, axis=(-2, -1))
    vg = kg if v_is_k else deq(v_pages, v_scale)
    acc = jnp.einsum("bhgsp,bshpd->bhgd", pexp, vg)
    return acc, m, l


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "win_slots", "v_is_k")
)
def paged_attn_xla(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: Optional[jnp.ndarray],
    tables: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    scale: float,
    window: int = 0,
    win_slots: int = 0,
    q2: Optional[jnp.ndarray] = None,
    k2_pages: Optional[jnp.ndarray] = None,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    k2_scale: Optional[jnp.ndarray] = None,
    v_is_k: bool = False,
) -> jnp.ndarray:
    """Gathered reference: materializes the ``(B, n_slots·ps, ...)`` view
    (exactly what the kernel exists to avoid) and applies the same
    per-position masks.  Parity oracle + off-TPU fallback for callers that
    already hold kernel-layout operands."""
    acc, m, l = _gathered_stats(
        q, k_pages, v_pages, tables, lengths, scale=scale, window=window,
        win_slots=win_slots, q2=q2, k2_pages=k2_pages, v_is_k=v_is_k,
        k_scale=k_scale, v_scale=v_scale, k2_scale=k2_scale,
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "win_slots", "v_is_k")
)
def paged_attn_stats_xla(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: Optional[jnp.ndarray],
    tables: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    scale: float,
    window: int = 0,
    win_slots: int = 0,
    q2: Optional[jnp.ndarray] = None,
    k2_pages: Optional[jnp.ndarray] = None,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    k2_scale: Optional[jnp.ndarray] = None,
    v_is_k: bool = False,
):
    """Stats-form gathered path: same math as :func:`paged_attn_xla` with
    the final divide left to the caller (the shard_map combine)."""
    return _gathered_stats(
        q, k_pages, v_pages, tables, lengths, scale=scale, window=window,
        win_slots=win_slots, q2=q2, k2_pages=k2_pages, v_is_k=v_is_k,
        k_scale=k_scale, v_scale=v_scale, k2_scale=k2_scale,
    )


dispatch.register(
    "paged_attn", "pallas", functools.partial(paged_attn_pallas, interpret=False)
)
dispatch.register(
    "paged_attn", "interpret", functools.partial(paged_attn_pallas, interpret=True)
)
dispatch.register("paged_attn", "xla", paged_attn_xla)

# stats-emitting variant: the per-shard inner kernel of the shard_map route
# (kernels.sharded).  Same grid walk; normalization deferred to the combine.
dispatch.register(
    "paged_attn_stats", "pallas",
    functools.partial(paged_attn_pallas, interpret=False, emit_stats=True),
)
dispatch.register(
    "paged_attn_stats", "interpret",
    functools.partial(paged_attn_pallas, interpret=True, emit_stats=True),
)
dispatch.register("paged_attn_stats", "xla", paged_attn_stats_xla)
