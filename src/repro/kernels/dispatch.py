"""Backend-aware kernel dispatch: one registry for every kernel call.

Why this exists: the seed wired ``nm_spmm_pallas(..., interpret=True)``
defaults straight into the serving matmul and left the CPU production path
on a ``put_along_axis`` scatter-decompress — running either a Pallas kernel
under the Python interpreter or an XLA scatter inside the decode hot loop.
That is how compressed decode measured ~8x *slower* than dense at batch 1
on the CPU.  Kernel routing belongs in one place, decided
by backend + shape, never hardcoded at a call site.

Modes
-----
- ``"pallas"``    — the compiled Pallas-TPU kernel (backend == "tpu").
- ``"interpret"`` — the same kernel body under the Pallas interpreter.
  Correctness-only: tests and debugging.  Never a production route.
- ``"xla"``       — a vectorized pure-XLA implementation.  The production
  path on CPU/GPU and the parity oracle everywhere.
- ``"shard_map"`` — a ``jax.shard_map`` wrapper that runs the
  kernel *per shard* over mesh-partitioned operands and combines partial
  results with tiny psums (``kernels.sharded``).  Selected automatically
  for ``shards > 1`` calls (see below); forcing it on an unsharded call
  falls through to the backend default.

Resolution order, first hit wins:

1. an explicit ``mode=...`` argument at the call site,
2. the innermost active :func:`force_mode` context (tests),
3. the ``REPRO_KERNEL_MODE`` environment variable (CI / smoke runs),
4. a per-kernel *shape guard* — shapes the Pallas grid cannot tile
   efficiently (e.g. a reduction dim whose only valid block size is
   degenerate) fall back to ``"xla"`` even on TPU,
5. the backend default: ``tpu -> "pallas"``, anything else ``-> "xla"``.

One override sits above all of these: ``shards > 1`` in the shape info
(operands partitioned across a mesh, e.g. a mesh-native engine's paged
pool — see ``PagedLayout.shards``) re-routes any non-``"xla"`` pick,
because a raw Pallas body is opaque to GSPMD and cannot be partitioned.
When a ``"shard_map"`` wrapper is registered for the kernel, a mesh is
active (:func:`mesh_context` — the mesh-native engine installs it around
every executable call), and the per-kernel *shard guard* accepts the
shape (divisibility: pages per shard, whole N:M groups per shard), the
call routes to the wrapper — the kernel runs per shard on shard-local
operands and the partial results combine with the same tiny psums the
XLA gathered path uses.  Otherwise ``"xla"`` remains the correctness
backstop: GSPMD partitions the gathered implementation.  The mode that
would have been picked without the override (forced ``"interpret"``, the
TPU ``"pallas"`` default, ...) becomes the *inner* per-shard route,
resolved by the wrapper through this same registry.

Resolution happens at trace time: a jitted caller bakes the route into its
executable, so flipping the env var after an engine compiled its decode
step does not re-route that engine (build a new one, as ``scripts/smoke.sh``
does for the forced-XLA serve invocation).  :func:`record_routes` counts
every resolution made inside it, so a caller can report which route each
kernel actually took — including a shape guard's quiet XLA pick on a TPU.

Registered kernels: ``nm_spmm`` (compressed N:M matmul), ``paged_attn``
(paged decode attention), ``nm_mask`` (fused mask-compute-and-apply; the
training-loop hot spot).  The legacy ``prefer_pallas``/``interpret`` knobs
that ``kernels.ops`` carried from the seed are retired — call sites pass
``mode=`` or rely on the resolution order above.
"""
from __future__ import annotations

import contextlib
import os
from typing import Callable, Optional

import jax

ENV_VAR = "REPRO_KERNEL_MODE"
MODES = ("pallas", "interpret", "xla", "shard_map")

_REGISTRY: dict[str, dict[str, Callable]] = {}
_GUARDS: dict[str, Callable[..., bool]] = {}
_SHARD_GUARDS: dict[str, Callable[..., bool]] = {}
_FORCED: list[str] = []
_MESHES: list = []  # trace-time mesh stack for the shard_map route
_RECORDERS: list = []  # active record_routes counters


def register(kernel: str, mode: str, fn: Callable) -> None:
    """Register ``fn`` as the ``mode`` implementation of ``kernel``."""
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}; expected one of {MODES}")
    _REGISTRY.setdefault(kernel, {})[mode] = fn


def register_guard(kernel: str, guard: Callable[..., bool]) -> None:
    """``guard(**shape_info) -> bool``: may the Pallas route take this shape?"""
    _GUARDS[kernel] = guard


def register_shard_guard(kernel: str, guard: Callable[..., bool]) -> None:
    """``guard(**shape_info) -> bool``: may the shard_map route take this
    sharded call?  (Divisibility checks: the wrapper's in_specs split
    operand dims exactly — pages per shard, whole N:M groups per shard.)"""
    _SHARD_GUARDS[kernel] = guard


@contextlib.contextmanager
def mesh_context(mesh):
    """Make ``mesh`` available to trace-time resolution: ``shards > 1``
    calls inside the context may route to a registered shard_map wrapper
    (which needs the concrete mesh to build its ``shard_map``).  The
    mesh-native serving engine installs this around every executable call;
    without it, sharded calls take the XLA backstop exactly as before."""
    _MESHES.append(mesh)
    try:
        yield
    finally:
        _MESHES.pop()


@contextlib.contextmanager
def record_routes(counter):
    """Count every :func:`resolve` made inside the context (trace time)
    as ``counter[(kernel, mode)] += 1``; yields ``counter``."""
    _RECORDERS.append(counter)
    try:
        yield counter
    finally:
        _RECORDERS.remove(counter)


def active_mesh():
    """The innermost :func:`mesh_context` mesh, or None."""
    return _MESHES[-1] if _MESHES else None


def registered() -> dict[str, tuple[str, ...]]:
    """kernel name -> modes with an implementation (introspection / tests)."""
    _ensure_registered()
    return {k: tuple(sorted(v)) for k, v in _REGISTRY.items()}


@contextlib.contextmanager
def force_mode(mode: str):
    """Force every dispatch inside the context to ``mode`` (tests)."""
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}; expected one of {MODES}")
    _FORCED.append(mode)
    try:
        yield
    finally:
        _FORCED.pop()


def _env_mode() -> Optional[str]:
    mode = os.environ.get(ENV_VAR, "").strip().lower()
    if not mode:
        return None
    if mode not in MODES:
        raise ValueError(f"{ENV_VAR}={mode!r}; expected one of {MODES}")
    return mode


def _ensure_registered(kernel: str = "") -> None:
    """Implementations self-register at import; pull their modules in."""
    if (
        "nm_spmm" not in _REGISTRY
        or "paged_attn" not in _REGISTRY
        or "nm_mask" not in _REGISTRY
        or "shard_map" not in _REGISTRY.get("paged_attn", {})
    ):
        import repro.kernels.nm_mask  # noqa: F401
        import repro.kernels.nm_spmm  # noqa: F401
        import repro.kernels.paged_attn  # noqa: F401
        import repro.kernels.sharded  # noqa: F401


def _default_mode(kernel: str, **shape_info) -> str:
    picked = "pallas" if jax.default_backend() == "tpu" else "xla"
    guard = _GUARDS.get(kernel)
    if picked == "pallas" and guard is not None and not guard(**shape_info):
        picked = "xla"  # shape the Pallas grid can't tile: use XLA even on TPU
    return picked


def _shard_route_ok(kernel: str, impls: dict, shape_info: dict) -> bool:
    """May this ``shards > 1`` call take the registered shard_map wrapper?
    Needs the wrapper, an active :func:`mesh_context` whose model axis
    matches the shard count, and the kernel's shard guard's blessing."""
    if "shard_map" not in impls:
        return False
    mesh = active_mesh()
    if mesh is None:
        return False
    from repro.distributed.sharding import MODEL_AXIS

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if int(sizes.get(MODEL_AXIS, 1)) != int(shape_info.get("shards", 1)):
        return False
    guard = _SHARD_GUARDS.get(kernel)
    return guard is None or bool(guard(**shape_info))


def resolve(kernel: str, mode: Optional[str] = None, **shape_info) -> tuple[str, Callable]:
    """Pick ``(mode, impl)`` for one kernel call.  See module docstring."""
    _ensure_registered(kernel)
    impls = _REGISTRY[kernel]
    picked = mode or (_FORCED[-1] if _FORCED else None) or _env_mode()
    if picked is None:
        picked = _default_mode(kernel, **shape_info)
    if shape_info.get("shards", 1) > 1 and picked != "xla":
        # mesh-partitioned operands: a raw Pallas body is opaque to GSPMD.
        # Route to the shard_map wrapper (per-shard kernel on shard-local
        # operands + psum combine) when one is registered and eligible;
        # the GSPMD-partitionable XLA implementation is the correctness
        # backstop.  Forced/env modes are overridden here too — they
        # become the *inner* per-shard route inside the wrapper instead.
        if _shard_route_ok(kernel, impls, shape_info):
            picked = "shard_map"
        elif "xla" in impls:
            picked = "xla"
    elif picked == "shard_map":
        # forced shard_map on an unsharded call (env-forced sweeps hit
        # every kernel): nothing to wrap — fall through to the default
        picked = _default_mode(kernel, **shape_info)
    if picked not in impls:
        raise NotImplementedError(f"kernel {kernel!r} has no {picked!r} impl")
    for counter in _RECORDERS:
        counter[(kernel, picked)] += 1
    return picked, impls[picked]


def uses_kernel(kernel: str, mode: Optional[str] = None, **shape_info) -> bool:
    """True when dispatch would run the fused Pallas kernel body (compiled
    or interpreted) rather than the XLA reference.  Call sites that must
    *restructure* around the kernel (e.g. paged decode skipping the
    contiguous gather) branch on this at trace time."""
    return resolve(kernel, mode, **shape_info)[0] != "xla"


# ---------------------------------------------------------------------------
# public kernel entry points
# ---------------------------------------------------------------------------


def nm_spmm(
    x, values, indices, n: int, m: int, *, o_true: Optional[int] = None,
    shards: int = 1, oshards: int = 1, mode: Optional[str] = None,
):
    """Compressed N:M matmul ``y = x @ decompress(values, indices)``.

    ``o_true`` slices off compress-time MXU padding on the output dim
    (``sparse_infer.compress_params`` stores lane-aligned buffers; the true
    width rides on ``CompressedTensor.pad``).

    ``shards``: how many model-axis shards partition the *group* (reduction)
    axis of ``values``/``indices`` (``CompressedTensor.rshards``, stamped by
    ``distributed.compressed_pspecs.annotate_tp``).  With
    ``shards > 1`` and an active :func:`mesh_context` the call routes to
    the per-shard shard_map wrapper (``kernels.sharded.nm_spmm_shard_map``:
    whole N:M groups per shard by construction, partial outputs
    psum-reduced); otherwise GSPMD partitions the XLA path.
    ``oshards`` is the same for an output-dim-sharded leaf
    (``CompressedTensor.oshards``): each shard computes its own output
    columns, no combine.  At most one of the two exceeds 1.
    """
    picked, fn = resolve(
        "nm_spmm", mode, b=x.shape[0], k=x.shape[-1], o=values.shape[-1],
        n=n, m=m, shards=max(shards, oshards), out_sharded=oshards > 1,
    )
    if picked == "shard_map":
        return fn(
            x, values, indices, n, m, o_true=o_true, mesh=active_mesh(),
            out_sharded=oshards > 1,
        )
    return fn(x, values, indices, n, m, o_true=o_true)


def nm_mask(w, n: int, m: int, *, mode: Optional[str] = None):
    """Fused N:M mask computation + application: ``(Π, Π⊙w)``.

    The Pallas kernel tiles 2-D weights with whole N:M groups running down
    the rows (axis 0 — the matmul reduction axis); other ranks/shapes are
    rare and small in the zoo and take the XLA reference on every mode, so
    a forced ``pallas``/``interpret`` run never hits the kernel's shape
    asserts mid-sweep.
    """
    if w.ndim != 2 or w.shape[0] % m:
        mode = "xla"
    _, fn = resolve("nm_mask", mode, ndim=w.ndim, rows=w.shape[0], m=m)
    return fn(w, n, m)


def paged_attn(
    q, k_pages, v_pages, tables, lengths, *, scale: float,
    window: int = 0, win_slots: int = 0, q2=None, k2_pages=None,
    k_scale=None, v_scale=None, k2_scale=None,
    v_is_k: bool = False, shards: int = 1, mode: Optional[str] = None,
):
    """Paged decode attention over a ``(P, Hkv, ps, D)`` pool + page table.

    See ``kernels.paged_attn`` for the argument contract (GQA and
    MLA-latent layouts, sentinel slots, windowed modular tables).
    ``k_scale``/``v_scale``/``k2_scale`` are the int8 pool's per-(page,
    slot) dequantization planes (``PagedLayout.quant``); every route —
    Pallas, interpret, the XLA gathered twin, and the shard_map stats
    variant — applies them per page under the same flash math.

    ``shards``: how many mesh shards partition the pool's pages axis
    (``PagedLayout.shards``).  With ``shards > 1`` and an active
    :func:`mesh_context`, the call routes to the shard_map wrapper
    (``kernels.sharded.paged_attn_shard_map``): each shard remaps the
    replicated table to shard-local page ids, runs the kernel over its
    slice of the pool emitting unnormalized flash ``(acc, m, l)`` stats,
    and the softmax combines via tiny psums — the same stats/psum shape
    GSPMD derives for the XLA gathered path, which remains the backstop
    when no mesh is active or the pool doesn't split evenly.
    """
    picked, fn = resolve(
        "paged_attn", mode, b=q.shape[0], n_slots=tables.shape[1],
        page_size=k_pages.shape[2], num_pages=k_pages.shape[0],
        shards=shards,
    )
    kw = dict(
        scale=scale, window=window, win_slots=win_slots, q2=q2,
        k2_pages=k2_pages, v_is_k=v_is_k,
        k_scale=k_scale, v_scale=v_scale, k2_scale=k2_scale,
    )
    if picked == "shard_map":
        kw["mesh"] = active_mesh()
    return fn(q, k_pages, v_pages, tables, lengths, **kw)
