"""Compressed N:M structured-sparse matmul (serving decode path).

TPUs have no Sparse-Tensor-Core analogue, but decode is HBM-bandwidth-bound:
the win from a learned N:M mask on TPU is reading only the kept N/M of the
weights from HBM (DESIGN.md §3).  The Pallas kernel streams compressed
tiles — values ``(K·N/M, O)`` + 8-bit in-group indices — into VMEM,
decompresses the tile *inside* VMEM with unrolled select ops, and feeds the
dense MXU.

Bandwidth model (per weight, counted on the ``gpt2-paper`` smoke artifact —
see ``weight_bytes_per_step`` in the serving engine's stats):

    HBM weight traffic per tile:  (N/M)·(bits_w + 8)/bits_w of dense
    (2:4 bf16: 0.75x;  1:4: 0.375x;  2:8 int8 would be 0.5x)

    gpt2-paper smoke, 2:4 bf16: 210_944 weight bytes/decode-step compressed
    vs 268_288 dense (0.786x — embeddings stay dense; matmul weights alone
    are 0.75x).  The same ratio bounds the achievable decode-step speedup
    at batch 1, where weight streaming dominates the step.  On the CPU
    bench the dispatch fix alone flipped compressed decode from 8.2x
    *slower* than dense (14_492 µs vs 1_764 µs/step, the seed pathology)
    to parity-or-faster at batch 1 (1_927 vs 2_180 µs and 1_377 vs
    1_308 µs across runs) and within 2x at batches 2-4.

Routing (see ``kernels.dispatch``): the compiled kernel serves TPU; CPU/GPU
use :func:`nm_spmm_xla` below.  Nothing in the hot loop runs the Pallas
interpreter — the seed's ``interpret=True`` default was how compressed
decode measured ~8x slower than dense on CPU.

Pallas schedule: grid (i, j, k) over (rows of x / BM, cols of W / BO,
reduction / BK) with a f32 VMEM accumulator; k is the innermost
(sequential) dimension and the accumulator is flushed at k == K-1 — the
standard Pallas TPU matmul schedule.  Blocks: BM=128, BO=256, BK=512
dense-rows (=> 512·N/M compressed rows), MXU-aligned.  Block sizes are
picked by gcd (no decrement-until-divides scan), and ``values``/``indices``
are expected pre-padded to lane alignment by ``sparse_infer.
compress_params`` — the runtime ``jnp.pad`` survives only as a fallback for
ad-hoc (test) shapes and artifacts compressed without TPU alignment (see
``compress_params(align=...)`` for the cross-backend export caveat); a
TPU-exported artifact never re-pads per call.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import dispatch


def pick_bk(k: int, n: int, m: int, target: int = 512) -> int:
    """Reduction block size: a divisor of ``k`` that keeps the compressed
    row count ``bk·n/m`` integral, picked via gcd in O(1).

    ``bk·n % m == 0``  iff  ``bk % (m / gcd(n, m)) == 0``; with ``q`` that
    quotient, valid block sizes are exactly the multiples of ``q`` dividing
    ``k``, and the pick is ``q · gcd(k/q, target/q)`` — no decrementing
    scan, and no near-prime ``bk`` that a scan could land on.  Shapes whose
    best pick is still tiny are routed to the XLA path by the dispatch
    guard instead of running a degenerate grid.
    """
    q = m // math.gcd(n, m)
    if k % q:
        raise ValueError(f"k={k} not divisible by m/gcd(n,m)={q}")
    return q * math.gcd(k // q, max(target // q, 1))


def _pick_block(dim: int, target: int) -> int:
    """Lane-dim block size: a gcd-divisor of ``dim`` when one of MXU size
    exists (no runtime pad), else ``target`` itself — a non-divisor, which
    makes the caller pad ``dim`` up.  Keeps unaligned ad-hoc widths (e.g. a
    vocab head) on the Pallas route at the cost of the pad the exported,
    compress-time-aligned artifacts never pay."""
    if dim <= target:
        return dim
    g = math.gcd(dim, target)
    return g if g >= 128 else target


def pallas_shape_ok(b: int, k: int, o: int, n: int, m: int) -> bool:
    """Dispatch guard: can the Pallas grid tile this shape non-degenerately?

    Requires whole groups along the reduction dim and a reduction block of
    at least one MXU tile (128) — smaller picks mean a pathological K
    (e.g. 2·prime) that the XLA path handles better than a bk=2 grid
    would.  The output dim never rejects: unaligned widths fall back to a
    runtime pad inside :func:`nm_spmm_pallas`.  The pick is the kernel's
    own (target ``min(512, k)``), so a K up to 512 is one whole block —
    e.g. the 192-wide per-shard K of a 4-way reduction-TP'd 768 matmul.
    """
    return k % m == 0 and pick_bk(k, n, m, min(512, k)) >= min(k, 128)


def _nm_spmm_kernel(x_ref, v_ref, i_ref, o_ref, acc_ref, *, n: int, m: int, bk: int):
    """x (BM, BK) @ decompress(v, i) (BK, BO) -> o (BM, BO)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    vals = v_ref[...].astype(jnp.float32)  # (BK*n/m, BO)
    idx = i_ref[...].astype(jnp.int32)
    g = bk // m  # dense groups in this block
    bo = vals.shape[-1]
    valsg = vals.reshape(g, n, bo)
    idxg = idx.reshape(g, n, bo)
    # decompress in VMEM: dense[g, r, o] = sum_j (idx[g, j, o] == r) * vals[g, j, o]
    row = jax.lax.broadcasted_iota(jnp.int32, (g, m, bo), 1)
    dense = jnp.zeros((g, m, bo), jnp.float32)
    for j in range(n):  # unrolled: n is static
        dense = dense + jnp.where(
            idxg[:, j : j + 1, :] == row, valsg[:, j : j + 1, :], 0.0
        )
    w = dense.reshape(bk, bo)
    acc_ref[...] += jax.lax.dot(
        x.astype(jnp.float32), w, preferred_element_type=jnp.float32
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("n", "m", "bm", "bo", "bk", "o_true", "interpret"),
)
def nm_spmm_pallas(
    x: jnp.ndarray,  # (B, K)
    values: jnp.ndarray,  # (K*n/m, O) — O pre-padded to lane alignment
    indices: jnp.ndarray,  # (K*n/m, O) uint8
    n: int,
    m: int,
    bm: int = 128,
    bo: int = 256,
    bk: int = 512,
    o_true: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """y = x @ decompress(values, indices); compressed weights never
    materialize densely in HBM.

    ``values``/``indices`` arrive MXU-aligned from compress time (see
    ``sparse_infer.compress_params``): block sizes are gcd-picks that
    divide the padded dims exactly, so no operand is re-padded here per
    call.  ``o_true`` strips the alignment columns from the result.
    """
    b, k = x.shape
    kc, o = values.shape
    assert kc * m == k * n, (k, kc, n, m)
    o_true = o if o_true is None else o_true
    bm = min(bm, b)
    bk = pick_bk(k, n, m, min(bk, k))
    bo = _pick_block(o, bo)
    bp = -(-b // bm) * bm
    op = -(-o // bo) * bo
    xp = jnp.pad(x, ((0, bp - b), (0, 0))) if bp != b else x
    if op != o:  # fallback for ad-hoc shapes; exported artifacts are aligned
        values = jnp.pad(values, ((0, 0), (0, op - o)))
        indices = jnp.pad(indices, ((0, 0), (0, op - o)))
    bkc = bk * n // m  # compressed rows per block
    grid = (bp // bm, op // bo, k // bk)
    out = pl.pallas_call(
        functools.partial(_nm_spmm_kernel, n=n, m=m, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bkc, bo), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bkc, bo), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bo), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, op), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bo), jnp.float32)],
        interpret=interpret,
    )(xp, values, indices)
    return out[:b, :o_true]


# ---------------------------------------------------------------------------
# XLA production path (CPU / GPU) — satellite of the dispatch refactor
# ---------------------------------------------------------------------------

# Below this many rows the activation-gather formulation beats
# decompress+matmul (CPU, 2:4 f32: at (1, 1024, 1024) gather 2.7ms vs
# decompress 4.2ms; the gather scales with rows and loses by ~20x at
# b=8 on 2048^2, where decompress+BLAS takes over).  Off-TPU the point is
# bounded damage, not a win: at serving-bench sizes compressed decode now
# matches-or-beats dense at batch 1 and stays within 2x above,
# while at >=1024^2 single-row
# shapes both formulations pay ~one decompress of traffic vs a GEMV —
# the bandwidth *win* needs the TPU kernel, which never decompresses to
# HBM at all.
GATHER_ROWS = 8


@functools.partial(jax.jit, static_argnames=("n", "m", "o_true"))
def nm_spmm_xla(
    x: jnp.ndarray,  # (B, K)
    values: jnp.ndarray,  # (K*n/m, O)
    indices: jnp.ndarray,  # (K*n/m, O) uint8
    n: int,
    m: int,
    o_true: int | None = None,
) -> jnp.ndarray:
    """Vectorized XLA compressed matmul — the production path off-TPU.

    Two regimes, chosen by (static) row count:

    - **decode** (``B <= GATHER_ROWS``): gather the activations each kept
      weight multiplies — ``x[b, g·m + idx[g,j,o]]`` — and reduce against
      ``values`` directly.  The dense weight is never materialized and the
      FLOP count is ~``3·(N/M)`` of the dense matmul (for 2:4 *fewer* ops
      than dense: this is what restores compressed-faster-than-dense on
      CPU, where the seed's scatter-decompress ref ran ~8x slower).
    - **prefill** (``B > GATHER_ROWS``): decompress with ``n`` unrolled
      compare/selects (the same schedule the Pallas kernel uses in VMEM)
      and hand the dense block to one BLAS matmul.

    Replaces ``put_along_axis`` decompression (XLA scatter: ~15x slower
    than either regime on CPU) everywhere except the oracle in ``ref.py``.
    """
    b, k = x.shape
    kc, o = values.shape
    assert kc * m == k * n, (k, kc, n, m)
    g = k // m
    o_true = o if o_true is None else o_true
    idx = indices.astype(jnp.int32).reshape(g, n, o)
    vals = values.astype(jnp.float32).reshape(g, n, o)
    if b <= GATHER_ROWS:
        xg = x.reshape(b, g, m)
        xsel = xg[:, jnp.arange(g)[:, None, None], idx]  # (B, g, n, O) gather
        y = jnp.einsum("bgno,gno->bo", xsel.astype(jnp.float32), vals)
    else:
        row = jax.lax.broadcasted_iota(jnp.int32, (g, m, o), 1)
        dense = jnp.zeros((g, m, o), jnp.float32)
        for j in range(n):  # unrolled: n is static
            dense = dense + jnp.where(
                idx[:, j : j + 1, :] == row, vals[:, j : j + 1, :], 0.0
            )
        y = x.astype(jnp.float32) @ dense.reshape(k, o)
    return y[:, :o_true].astype(x.dtype)


def _pallas_entry(x, values, indices, n, m, o_true=None, *, interpret):
    return nm_spmm_pallas(
        x, values, indices, n, m, o_true=o_true, interpret=interpret
    )


dispatch.register(
    "nm_spmm", "pallas", functools.partial(_pallas_entry, interpret=False)
)
dispatch.register(
    "nm_spmm", "interpret", functools.partial(_pallas_entry, interpret=True)
)
dispatch.register("nm_spmm", "xla", nm_spmm_xla)
dispatch.register_guard(
    "nm_spmm", lambda b, k, o, n, m, **_: pallas_shape_ok(b, k, o, n, m)
)
