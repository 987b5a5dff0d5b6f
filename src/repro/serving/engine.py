"""Continuous-batching decode engine over a (compressed or dense) model tree.

The engine is the serving counterpart of ``launch/train.py``'s Trainer: it
owns the KV-cache (a per-lane slab, or a block-granular paged pool — see
below), a FIFO request queue, and the jitted prefill/decode executables,
and it serves the parameter tree it is given *as is*.  Hand it the
N:M-compressed artifact from ``sparse_infer.compress_params`` and every
weight matmul inside prefill / decode routes through the compressed
``nm_spmm`` path (see ``models.layers.matmul``) — the dense weights never
materialize in HBM.

Scheduling: dispatch-boundary continuous batching
-------------------------------------------------
The decode hot loop is **fused and zero-copy**: one jitted dispatch runs
``steps_per_dispatch`` (K) decode steps as an on-device ``lax.scan`` that
embeds, attends, samples, scatters each new token into the cache, and
advances ``cache["len"]`` — K tokens per lane move device→host as a single
``(K, max_batch)`` block, so the host is consulted once per K tokens
instead of once per token.  The cache pytree and token buffer are
**donated** (``donate_argnums``) into the decode, prefill, and
chunked-prefill executables, so XLA updates the paged pool in place
instead of copying every cache buffer per call; pass ``donate=False`` for
the copying baseline (bit-identical streams, strictly more HBM traffic).

All scheduling happens at **dispatch boundaries**: queued requests are
admitted (batched bucketed prefill), finished lanes retire, and — under
pool pressure — preemption victims are chosen, only between dispatches.
Mid-scan, per-lane stop detection runs **on device**
(``sampling.advance_stops``): a lane that emits its EOS, exhausts its
``max_new_tokens`` budget, or hits the logical capacity freezes (stops
sampling, stops writing, stops advancing its length) until the host
replays the same rules over the token block at the boundary.  The paged
pool pre-reserves every page the K writes need (``ensure_steps``) before
the dispatch, so mid-scan pool exhaustion cannot occur.  Per-slot
``cache["len"]`` keeps heterogeneous sequence positions correct; idle
lanes are pinned to length 0 and their sampled tokens discarded.

**Chunked prefill** (``prefill_chunk=N``): a prompt longer than N tokens
no longer head-of-line-blocks in-flight decodes behind one monolithic
prefill — it is absorbed N tokens at a time, one chunk per scheduling
step, interleaved with the decode dispatches of the running lanes; the
final chunk samples the request's first token and the lane joins the next
decode dispatch.  Attention-family archs only (recurrent state cannot
resume mid-prompt).  Sliding-window archs chunk on the *paged* layout:
each chunk reads a windowed ring view of the cache
(``cache.PagedLayout.attn_chunk_view_win``) and maps its window-ring
pages chunk-by-chunk (``alloc_prefill(defer_win=True)`` at admission,
``ensure_steps`` per chunk), so a window that slides during the prompt
stays collision-free as long as the pool's ``lookahead >= chunk``.
Slab windowed prompts keep whole-prompt prefill.

Device-resident scheduling (run-until-stop, refill, async streams)
------------------------------------------------------------------
``max_steps_per_dispatch=K`` swaps the fixed-K ``lax.scan`` for an
on-device ``lax.while_loop``: the loop decodes until **some lane
freezes** (``sampling.advance_stops`` decides continuation on device) or
the K-step bound, so short answers stop syncing the host every K tokens
and long answers amortize one host sync over up to K·B tokens.  Sampling
keys are a pure function of ``(request uid, generated-token index)``
(``sampling.request_keys``), so streams — greedy *and* sampled — are
bit-identical to the fixed-K sync scheduler no matter how dispatches are
cut.

``staged_lanes=Q`` pre-stages up to Q queued prompts on device: their
token buffers and pre-reserved page-table rows
(``kv_pool.PagedKVPool.stage_alloc``) ride along in the scheduler state,
and when a lane freezes mid-loop the while-loop swaps a staged request
into the dead lane — table rows installed, recurrent state zeroed
(``model.reset_lanes``), prompt fed token-by-token from the staged
buffer — and starts its prefill **inside the same dispatch**.  The host
finds out at the next sync (``consumed_lane``/``consumed_step``) and
replays the swap in its bookkeeping.

``async_stream=True`` double-buffers dispatches: two while-loop calls
are enqueued back-to-back (the scheduler state and cache chain device
side), so dispatch N+1 executes while the host fetches and replays
dispatch N's token block — decode never waits on a host read.  All host
mutations (admission, staging, page reservation, table sync) happen only
at full-drain cycle boundaries, which is what keeps the
never-write-unmapped invariant without mid-flight synchronization; the
host-side stop replay is unchanged, so streams stay bit-identical.

Cache layouts
-------------
``DecodeEngine`` runs over either cache layout behind the
``models.cache.CacheLayout`` seam:

- **slab** (default): one contiguous ``(max_batch, max_len, ...)`` slab per
  attention/MLA layer.  Admission = a free lane; a request that outgrows
  ``max_len`` finishes with ``finish_reason="cache_full"``.
- **paged** (pass ``num_pages``/``page_size`` or a prebuilt
  ``kv_pool.PagedKVPool``): each layer owns a ``(num_pages, page_size, ...)``
  pool and per-lane *page tables* map logical token positions to physical
  pages.  Admission requires a free lane *and* enough free pages for the
  prompt; page tables grow on demand as lanes decode, and the device copy
  is synced **incrementally** — only lanes whose rows changed since the
  last dispatch are scattered into the resident table arrays
  (``PagedKVPool.device_tables``), never a full re-upload per step.  When
  the pool runs dry at a dispatch boundary the engine **preempts** the
  youngest lane instead of truncating: its pages are freed, and the
  request is re-queued at the front with its generated-so-far tokens as a
  resume prefix — on re-admission it re-prefills ``prompt + prefix`` and
  continues.  ``finish_reason="cache_full"`` survives only for the logical
  per-request capacity ``max_len`` (the page-table width), never for pool
  pressure.  The host-side allocator lives in ``serving.kv_pool``.

Prefill is **bucketed and batched**: queued prompts admitted in the same
scheduling step are padded to a small static set of bucket lengths (powers
of two up to ``max_len`` by default) and each bucket group is prefilled in
one jitted call.  Architectures with recurrent state (SSM / RG-LRU) cannot
absorb padding tokens into their state, so they group by *exact* prompt
length instead — still one batched prefill per group.  Chunked prefill is
batched the same way: one chunk dispatch per scheduling step absorbs a
chunk of *every* currently-chunking lane.

Mesh-native serving
-------------------
Pass ``mesh=`` (a ``("data", "model")`` mesh, e.g. from
``launch.mesh.make_local_mesh``) and the engine becomes tensor-parallel
end to end: every executable — prefill, chunked prefill, and the K-step
decode scan — is jitted with **explicit in/out NamedShardings**, and the
live params / cache / token buffer are ``device_put`` to match, so GSPMD
partitions the whole serving path instead of replicating it.

- **Weights** are TP-sharded by the serving pspec seam
  (``distributed.compressed_pspecs``): dense leaves follow the training
  rules with FSDP off (decode reads every weight each step), and each
  ``CompressedTensor`` leaf derives its spec from the dense rule for the
  same name — TP on the non-compressed (output) dim by default, on the
  compressed (reduction) dim only when the dense dim divides by
  ``M × axis_size`` so no N:M group straddles a shard.  Per-leaf
  ``sanitize_spec`` degrades odd dims to replication instead of erroring.
  The compressed artifact is served *sharded*: no dense or
  fully-replicated weight leaf is ever materialized (inspect with
  :meth:`sharding_report`).
- **KV caches are sequence-sharded** on the ``model`` axis
  (``kv_shard="seq"``, the ``cache_pspecs`` rule measured 75x cheaper in
  collectives than head-sharding): slab caches split the per-lane
  sequence axis; the paged pool splits its *pages* axis, so each shard
  physically owns a slice of the pool while the (replicated) page tables
  resolve logical→physical addresses locally on every shard.  Decode
  attention computes per-shard partial flash stats and the softmax
  combines via tiny psums — only ``(B, H)``-sized stats cross the
  interconnect, never cache pages.  The engine installs a
  ``kernels.dispatch.mesh_context`` around every executable call, so
  sharded pools (``PagedLayout.shards > 1``) route to the shard_map
  wrapper (``kernels.sharded``: per-shard table remap + the Pallas grid
  walk + an explicit flash-stat combine) whenever the inner route is a
  kernel body; the GSPMD-partitioned XLA gathered path remains the
  correctness backstop and the off-TPU default.  Tensor-parallel
  compressed leaves are stamped with their shard count (``annotate_tp``:
  reduction or output axis) so ``nm_spmm`` takes the per-shard route the
  same way.  :meth:`kernel_route` reports the resolved paged route and
  :meth:`kernel_routes` every route the executables traced.
- **Degenerate 1×1 meshes are bit-identical** to the mesh-less engine:
  every sharding becomes trivial and the executables lower to the exact
  single-device programs, so ``mesh=None`` and a one-device mesh (and, in
  practice, any mesh shape — locked by tests/test_sharded_serving.py)
  produce the same greedy token streams.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
import warnings
from collections import Counter, deque
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.cache import SlabLayout
from repro.models.model import (
    TransformerLM,
    _block_mixer_mlp,
    layer_plan,
    reset_lanes,
)
from repro.serving.kv_pool import PagedKVPool
from repro.serving.sampling import (
    SamplingParams,
    advance_stops,
    filtered_probs,
    request_keys,
    sample_tokens,
    spec_accept,
)
from repro.sparse_infer.compress import CompressedTensor


def _tree_stored_bytes(tree) -> int:
    """HBM bytes of a parameter tree as stored: ``CompressedTensor``
    leaves at their compressed (values + indices) size."""
    return sum(
        int(leaf.nbytes)
        for leaf in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, CompressedTensor)
        )
    )


@contextlib.contextmanager
def _quiet_donation():
    """Buffer donation is a no-op on backends without aliasing support
    (CPU); the stream is identical either way, so JAX's per-executable
    warning is noise — suppressed only around the engine's own dispatches
    (never globally: other code's donation bugs should still warn)."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        yield


@dataclasses.dataclass
class GenerationResult:
    """A completed request."""

    uid: int
    prompt: list[int]
    tokens: list[int]  # generated tokens (eos not included)
    finish_reason: str  # "eos" | "length" | "cache_full"


@dataclasses.dataclass
class _Request:
    uid: int
    prompt: list[int]
    sampling: SamplingParams
    # tokens generated before a preemption; on admission the engine
    # prefills prompt + prefix and generation continues after them
    prefix: list[int] = dataclasses.field(default_factory=list)


class _Slot:
    """Host-side bookkeeping for one active batch lane."""

    __slots__ = ("uid", "prompt", "sampling", "generated", "pos", "seq",
                 "pending", "feed")

    def __init__(self, req: _Request, pos: int, seq: int,
                 pending: Optional[list[int]] = None, feed: bool = False):
        self.uid = req.uid
        self.prompt = req.prompt
        self.sampling = req.sampling
        self.generated: list[int] = list(req.prefix)
        self.pos = pos  # host mirror of cache["len"][lane]
        self.seq = seq  # admission order; preemption evicts youngest first
        # chunked prefill: prompt(+prefix) tokens not yet absorbed into the
        # cache; the lane joins decode once this drains
        self.pending: list[int] = pending or []
        # device-scheduler refill: pending drains token-by-token *on
        # device* (fed from the staged buffer inside the while-loop), not
        # through the host's chunked-prefill dispatches
        self.feed = feed


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class DecodeEngine:
    """Batched decode over a slab or paged cache with continuous batching.

    Parameters
    ----------
    model: the ``TransformerLM`` wrapper.
    params: the serving tree — dense arrays and/or ``CompressedTensor``
        leaves; served directly, no rehydration.
    max_batch: number of concurrent decode lanes.
    max_len: logical per-request cache capacity (prompt + generated).
    kv_pool / num_pages / page_size: enable the paged layout — pass a
        prebuilt ``PagedKVPool`` or just ``num_pages`` (+ optional
        ``page_size``, default 16) to have the engine build one.
    steps_per_dispatch: decode steps fused into one on-device scan (K).
        The host syncs once per K tokens; admission/preemption happen at
        dispatch boundaries.  Greedy streams are bit-identical across K.
    max_steps_per_dispatch: enable the device-resident scheduler — the
        fixed-K scan becomes a run-until-stop ``lax.while_loop`` bounded
        by this many steps per dispatch (see "Device-resident
        scheduling" in the module docstring).  ``None`` (default) keeps
        the fixed-K sync scheduler.  Streams are bit-identical across
        schedulers.
    staged_lanes: device scheduler only — queued prompts pre-staged on
        device per cycle, so a lane that freezes mid-loop refills (and
        starts prefilling the staged prompt) inside the same dispatch.
        0 disables on-device refill.
    async_stream: device scheduler only — double-buffer dispatches: the
        next while-loop launches before the previous one's token block
        is fetched, so the host read overlaps device decode.
    donate: donate the cache pytree + token buffer into the jitted
        executables so the cache updates in place (no per-step full-cache
        copy).  ``False`` keeps the copying baseline; streams are
        bit-identical either way.
    prefill_chunk: absorb prompts longer than this in fixed-size chunks
        interleaved with decode dispatches (attention-family archs only;
        ignored for recurrent-state and sliding-window archs).
    prefill_buckets: static prompt-pad lengths for batched prefill
        (default: powers of two up to ``max_len``).  Ignored for archs
        with recurrent state, which group by exact prompt length.
    max_prefill_batch: cap on requests prefetched into one batched
        prefill (default ``max_batch``).
    mesh: optional ``("data", "model")`` mesh — serve tensor-parallel with
        sequence/pages-sharded KV caches (see "Mesh-native serving" in the
        module docstring).  A 1×1 mesh degenerates bit-identically to
        ``mesh=None``.
    kv_shard: ``"seq"`` (default; slab sequence axis / paged pages axis
        over ``model``) or ``"feature"`` (trailing head/latent dim) —
        the ``cache_pspecs`` layouts.  ``"feature"`` is rejected on
        meshes with a model axis > 1: its prefill write miscompiles under
        the SPMD partitioner (see ``compressed_pspecs.check_kv_shard``).
    prefix_cache: index every fully-prefilled prompt's pages in a radix
        trie (``serving.prefix_cache.PrefixIndex``); later requests
        sharing a prefix map the cached pages into their table (shared,
        refcounted, copy-on-write on divergence) and prefill only the
        uncached tail.  Paged + append-only + attention-family only;
        silently ignored (with a warning) otherwise.
    kv_quant: store KV pages as int8 with per-page-row scales (f16
        storage, f32 compute)
        (``models.cache.PagedLayout.quant``) — ~4x smaller pool at equal
        page count, dequantized inside the attention kernels.  Greedy
        streams may differ from fp pools within quantization tolerance.
    spec_gamma: enable self-speculative decoding — ``params`` becomes the
        *drafter* (the N:M-compressed artifact) and each scheduling step
        runs one speculative round: a gamma-step drafter scan proposes
        tokens per lane, then ONE chunked verify pass through
        ``verify_params`` scores all gamma+1 positions, accepts the
        longest valid draft prefix (greedy: argmax match; sampled: the
        standard rejection rule) and emits one trailing verifier token —
        so output distributions are *exactly* the verifier's, and greedy
        streams are bit-identical to plain decoding under
        ``verify_params``.  Pass an int >= 1 or ``"auto"`` (roofline pick,
        :meth:`pick_spec_gamma`).  Prefill / chunked prefill also run the
        verifier, so every committed KV entry is verifier-fidelity; the
        drafter's transient in-round KV writes are rewritten by the verify
        pass, and rejected tails are rolled back (``cache["len"]`` rewind
        on device + ``PagedKVPool.rollback`` host-side).  Sync scheduler
        + attention-family, non-windowed archs only.
    verify_params: the verifier tree for ``spec_gamma`` — the dense
        source weights, or a higher-fidelity N:M artifact (e.g. 4:8
        verifying a 2:4 drafter).  Mesh-native like ``params`` (its
        leaves take the serving pspec rules via
        ``verifier_param_shardings``).
    """

    def __init__(
        self,
        model: TransformerLM,
        params: Any,
        *,
        max_batch: int = 8,
        max_len: int = 128,
        seed: int = 0,
        kv_pool: Optional[PagedKVPool] = None,
        num_pages: Optional[int] = None,
        page_size: int = 16,
        steps_per_dispatch: int = 1,
        max_steps_per_dispatch: Optional[int] = None,
        staged_lanes: int = 0,
        async_stream: bool = False,
        donate: bool = True,
        prefill_chunk: Optional[int] = None,
        prefill_buckets: Optional[Sequence[int]] = None,
        max_prefill_batch: Optional[int] = None,
        mesh=None,
        kv_shard: str = "seq",
        prefix_cache: bool = False,
        kv_quant: bool = False,
        spec_gamma=None,
        verify_params: Any = None,
    ):
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.mesh = mesh
        self.kv_shard = kv_shard
        # (kernel, mode) -> resolutions made while tracing this engine's
        # executables (kernels.dispatch.record_routes; see kernel_routes)
        self._routes: Counter = Counter()
        if steps_per_dispatch < 1:
            raise ValueError(f"steps_per_dispatch must be >= 1, got {steps_per_dispatch}")
        self.steps_per_dispatch = steps_per_dispatch
        self.donate = donate
        # device-resident scheduler configuration.  The write horizon H is
        # the most positions any lane can append between two host syncs:
        # k_loop steps per dispatch times the number of in-flight
        # dispatches per cycle (2 when async double-buffering).  All page
        # reservation (live-lane runway and staged-refill exposure) is
        # sized by H, which is what keeps mid-loop writes on mapped pages.
        self._device = max_steps_per_dispatch is not None
        if self._device and max_steps_per_dispatch < 1:
            raise ValueError(
                f"max_steps_per_dispatch must be >= 1, got {max_steps_per_dispatch}"
            )
        if (staged_lanes or async_stream) and not self._device:
            raise ValueError(
                "staged_lanes/async_stream need the device scheduler: "
                "pass max_steps_per_dispatch="
            )
        if staged_lanes < 0:
            raise ValueError(f"staged_lanes must be >= 0, got {staged_lanes}")
        self.k_loop = max_steps_per_dispatch
        self.staged_lanes = staged_lanes
        self.async_stream = async_stream
        self._w = 2 if async_stream else 1
        self._horizon = (
            self.k_loop * self._w if self._device else steps_per_dispatch
        )
        # chunked-prefill gating must precede pool construction: windowed
        # chunking sizes the pool's window-ring lookahead by the chunk
        windowed_arch = model.cfg.local_window is not None
        plan = layer_plan(model.cfg)
        kinds = list(plan.head) + list(plan.period) * plan.n_body + list(plan.tail)
        # recurrent state cannot absorb pad tokens: group by exact length
        self._exact_prefill = any(
            _block_mixer_mlp(k, model.cfg)[0] in ("ssm", "rec") for k in kinds
        )
        # chunked prefill needs every mixer to read mid-prompt state from
        # the cache: attention-family only.  Windowed archs additionally
        # need the paged layout (the windowed chunk view reads the
        # window-ring page table; the slab has no ring to view)
        self._chunk_ok = (
            prefill_chunk is not None
            and not self._exact_prefill
            and (not windowed_arch or kv_pool is not None or num_pages is not None)
        )
        # -- speculative decoding: sparse drafter, higher-fidelity verifier --
        self._spec = spec_gamma is not None
        self._draft_params = None
        self.spec_gamma = 0
        if self._spec:
            if verify_params is None:
                raise ValueError(
                    "spec_gamma needs verify_params= — the dense (or "
                    "higher-fidelity N:M) tree the drafts are verified "
                    "against"
                )
            if windowed_arch:
                raise ValueError(
                    "spec_gamma is not supported on sliding-window archs: "
                    "a rejected draft cannot be rolled back out of the "
                    "window ring (slid-past pages are already evicted); "
                    "drop spec_gamma for this architecture"
                )
            if self._exact_prefill:
                raise ValueError(
                    "spec_gamma is not supported on SSM/RG-LRU archs: "
                    "recurrent state advanced by a rejected draft cannot "
                    "be rolled back; drop spec_gamma for this architecture"
                )
            if self._device:
                raise ValueError(
                    "spec_gamma needs the sync scheduler: drop "
                    "max_steps_per_dispatch/staged_lanes/async_stream "
                    "(a speculative round is already one host sync per "
                    "gamma+1 tokens)"
                )
            w_d = _tree_stored_bytes(params)
            w_v = _tree_stored_bytes(verify_params)
            if spec_gamma == "auto":
                spec_gamma = self.pick_spec_gamma(w_d, w_v)
            spec_gamma = int(spec_gamma)
            if spec_gamma < 1:
                raise ValueError(
                    f"spec_gamma must be >= 1 or 'auto', got {spec_gamma}"
                )
            if spec_gamma >= max_len:
                raise ValueError(
                    f"spec_gamma {spec_gamma} >= max_len {max_len}"
                )
            self.spec_gamma = spec_gamma
            self._spec_draft_bytes = w_d
            self._spec_verify_bytes = w_v
            # one round writes gamma+1 positions past the committed length
            # (gamma draft slots + the verify bonus slot): size the page
            # reservation horizon to cover the whole round, so
            # _ensure_capacity's per-lane clamp reserves exactly the pages
            # the round can touch and rollback releases the rejected tail
            self._horizon = max(self._horizon, spec_gamma + 1)
            # prefill, chunked prefill, and the verify pass all run the
            # *verifier* tree — every committed KV entry and every emitted
            # distribution is the verifier's; the drafter only steers
            # which tokens get proposed.  From here on self.params IS the
            # verifier and the drafter rides in _draft_params.
            self._draft_params = params
            params = verify_params
            self.params = params
        if kv_pool is None and num_pages is not None:
            lookahead = max(steps_per_dispatch, self._horizon)
            if self._chunk_ok and windowed_arch:
                # windowed chunk writes walk the window ring csz slots per
                # chunk; lookahead >= csz keeps them collision-free with
                # the positions the chunk view still reads
                lookahead = max(lookahead, prefill_chunk)
            kv_pool = PagedKVPool(
                model, max_batch=max_batch, max_len=max_len,
                num_pages=num_pages, page_size=page_size,
                lookahead=lookahead, mesh=mesh, kv_shard=kv_shard,
                quant=kv_quant,
            )
        if kv_quant and kv_pool is not None and not kv_pool.layout.quant:
            raise ValueError(
                "kv_quant=True needs a pool built with quant=True (pass "
                "quant= to PagedKVPool, or let the engine build it)"
            )
        self.pool = kv_pool
        if self.pool is not None:
            if self.pool.layout.lookahead < steps_per_dispatch:
                raise ValueError(
                    f"pool lookahead {self.pool.layout.lookahead} < "
                    f"steps_per_dispatch {steps_per_dispatch}; build the pool "
                    "with lookahead >= K"
                )
            if self._device and self.pool.layout.lookahead < self._horizon:
                raise ValueError(
                    f"pool lookahead {self.pool.layout.lookahead} < write "
                    f"horizon {self._horizon} (max_steps_per_dispatch x "
                    f"{self._w} in-flight dispatches); build the pool with "
                    "lookahead >= the horizon"
                )
            if (
                self._chunk_ok
                and windowed_arch
                and self.pool.layout.lookahead < prefill_chunk
            ):
                warnings.warn(
                    "windowed chunked prefill disabled: pool lookahead "
                    f"{self.pool.layout.lookahead} < prefill_chunk "
                    f"{prefill_chunk} (the window ring would recycle pages "
                    "the chunk view still reads)"
                )
                self._chunk_ok = False
            if mesh is not None and (
                self.pool.mesh is not mesh
                or getattr(self.pool, "kv_shard", kv_shard) != kv_shard
            ):
                raise ValueError(
                    "a mesh-native engine needs a pool built with the same "
                    "mesh and kv_shard (pass mesh=/kv_shard= to PagedKVPool, "
                    "or let the engine build it via num_pages=...)"
                )
            self.layout = self.pool.layout
            self.cache = self.pool.cache
        else:
            self.layout = SlabLayout(max_len)
            self.cache = model.init_cache(max_batch, max_len)
        # mesh-native serving: every executable below is jitted with explicit
        # in/out NamedShardings derived from the serving pspec seam, and the
        # live params / cache / token buffer are device_put to match.  A 1x1
        # mesh makes every sharding trivial, so the executables degenerate
        # bit-identically to the mesh=None path.
        self._shardings: Optional[dict] = None
        if mesh is not None:
            from repro.distributed.compressed_pspecs import (
                annotate_tp,
                check_kv_shard,
                lane_sharding,
                replicated,
                serving_cache_shardings,
                serving_param_shardings,
                verifier_param_shardings,
            )

            check_kv_shard(mesh, kv_shard)
            # stamp reduction-TP'd compressed leaves with their model-axis
            # shard count BEFORE deriving shardings: rshards lives in the
            # pytree aux, so the spec tree must be built from the annotated
            # tree to match leaf-for-leaf under device_put / in_shardings
            params = annotate_tp(params, mesh, cfg=model.cfg)
            self._shardings = {
                # in spec mode params is the *verifier*; its (dense or
                # compressed) leaves take the same serving placement seam
                "params": (
                    verifier_param_shardings(mesh, params, cfg=model.cfg)
                    if self._spec
                    else serving_param_shardings(mesh, params, cfg=model.cfg)
                ),
                # a mesh-native pool already derived (and applied) the
                # cache sharding tree — reuse it rather than re-walking
                "cache": (
                    self.pool.cache_shardings
                    if self.pool is not None
                    and self.pool.cache_shardings is not None
                    else serving_cache_shardings(
                        mesh, self.cache, self.layout, kv_shard=kv_shard
                    )
                ),
                "lane": lane_sharding(mesh, max_batch),
                "repl": replicated(mesh),
            }
            self.params = jax.device_put(params, self._shardings["params"])
            if self._spec:
                # the drafter tree is mesh-native too: same pspec seam, so
                # the draft scan and the verify pass run on one mesh with
                # no resharding between them
                dtree = annotate_tp(
                    self._draft_params, mesh, cfg=model.cfg
                )
                self._shardings["draft_params"] = serving_param_shardings(
                    mesh, dtree, cfg=model.cfg
                )
                self._draft_params = jax.device_put(
                    dtree, self._shardings["draft_params"]
                )
            if self.pool is None:
                self.cache = jax.device_put(self.cache, self._shardings["cache"])

        self.slots: list[Optional[_Slot]] = [None] * max_batch
        self.queue: deque[_Request] = deque()
        self.tokens = jnp.zeros((max_batch,), jnp.int32)
        if self._shardings is not None:
            self.tokens = jax.device_put(self.tokens, self._shardings["lane"])
        # the base sampling key is never split: per-token keys derive from
        # it as fold_in(fold_in(base, uid), token_index) (request_keys), so
        # streams are scheduler- and batch-mix-independent
        self.key = jax.random.PRNGKey(seed)
        self._next_uid = 0
        self._admit_seq = 0
        self.decode_steps = 0  # logical token steps actually executed
        self.dispatches = 0  # jitted decode calls
        self.cycles = 0  # device-scheduler cycles (full-drain host syncs)
        self.refills = 0  # on-device lane refills from the staged ring
        self.block_fetches = 0  # device->host token-block reads
        # staged-but-unconsumed queue entries for on-device refill:
        # [{"req": _Request, "rec": stage_alloc record | None,
        #   "tokens": np(S,), "len": int}] — rebuilt every cycle
        self._staged: list[dict] = []
        # seam for tests: how a device token block becomes host numpy
        # (forced-slow reads exercise async double-buffer ordering)
        self._fetch_block = lambda b: np.asarray(b)
        # inter-token latency: wall-clock deltas between consecutive
        # emissions of the same request, recorded at absorb time
        self._itl_ms: list[float] = []
        self._last_emit: dict[int, float] = {}
        self.admitted = 0
        self.preemptions = 0
        self.prefix_hits = 0  # admissions that reused cached prefix pages
        self.prefix_hit_tokens = 0  # prompt tokens skipped via the index
        # speculative-decoding accounting (spec_gamma only)
        self.spec_rounds = 0  # draft-scan + verify-pass round trips
        self.draft_tokens = 0  # tokens the drafter proposed
        self.verify_tokens = 0  # positions the verifier scored
        self.accepted_draft_tokens = 0  # proposals that survived verify
        self.spec_emitted_tokens = 0  # tokens actually absorbed via spec
        self._spec_req: dict[int, list[int]] = {}  # uid -> [drafted, accepted]
        self.max_concurrency = 0
        self.prefill_batches = 0
        self.prefill_chunks = 0  # chunked-prefill dispatches
        self.tokens_generated = 0
        self.decode_tokens = 0  # tokens produced by decode steps (not prefill)
        self.decode_wall_s = 0.0  # dispatch wall time (device + launch)
        self.sched_host_s = 0.0  # host scheduling time around dispatches
        self._util_sum = 0.0
        self._util_n = 0
        self._kv_bytes_sum = 0.0  # live KV bytes summed over decode steps
        self._kv_row_b: Optional[tuple[int, int]] = None  # _kv_row_bytes cache
        # slot-change-triggered host constants (temps/topks/eos/active/keep
        # and the static sampling flags are rebuilt only when the slot set
        # changes, not per dispatch)
        self._slots_dirty = True
        self._consts: Optional[dict] = None

        self.prefill_chunk = prefill_chunk if self._chunk_ok else None
        # windowed chunking maps window-ring pages chunk-by-chunk
        # (alloc_prefill defers them; _advance_chunks reserves per chunk)
        self._win_chunk = self.prefill_chunk is not None and windowed_arch
        # prefix caching rides the chunked-prefill machinery (a prefix-hit
        # lane is admitted as "already absorbed its first chunks" and the
        # uncached tail drains through _advance_chunks), so it carries the
        # same arch gate — attention-family, non-windowed — plus an
        # append-only full table (windowed pools evict shared pages).
        self._prefix = None
        if prefix_cache:
            lay = self.pool.layout if self.pool is not None else None
            if (
                lay is not None
                and lay.has_full and not lay.win
                and not self._exact_prefill
                and model.cfg.local_window is None
            ):
                from repro.serving.prefix_cache import PrefixIndex

                self._prefix = PrefixIndex(self.pool, lay.page_size)
            else:
                warnings.warn(
                    "prefix_cache=True ignored: needs a paged append-only "
                    "full table on an attention-family, non-windowed arch"
                )
        # tail prefill of a prefix hit reuses the chunk executable even when
        # chunked prefill itself is off — pick a chunk size for that case
        self._tail_chunk = self.prefill_chunk or min(64, max_len)
        if prefill_buckets:
            buckets = sorted(int(b) for b in prefill_buckets if 0 < int(b) <= max_len)
        else:
            buckets, b = [], 8
            while b < max_len:
                buckets.append(b)
                b *= 2
        if not buckets or buckets[-1] < max_len:
            buckets.append(max_len)
        self.prefill_buckets = tuple(buckets)
        self.max_prefill_batch = max_prefill_batch or max_batch

        layout = self.layout
        eng_max_len = max_len
        n_lanes = max_batch
        n_staged = max(1, staged_lanes)

        def _decode(params, tok, cache, temps, topks, active, keep, key,
                    eos, budget, uids, counts, k, need_sample, need_topk):
            # K decode steps fused into one on-device scan: embed → attend →
            # sample → scatter-into-cache → stop-detect, K times, one host
            # sync.  ``active`` lanes decode; ``keep`` lanes (occupied but
            # not decoding, e.g. mid chunked-prefill) hold their length;
            # free lanes pin to 0 so they cannot creep past the cache bound.
            # Sampling keys derive per row from (uid, generated-token
            # index); ``counts`` advances with each sampled token so the
            # stream is independent of how dispatches are cut.
            def body(carry, _):
                tok, cache, active, budget, counts = carry
                len_prev = cache["len"]
                logits, cache = model.decode_step(params, tok, cache, layout)
                cache["len"] = jnp.where(
                    active, cache["len"], jnp.where(keep, len_prev, 0)
                )
                keys = request_keys(key, uids, counts)
                nxt = sample_tokens(
                    logits, temps, topks, keys,
                    need_sample=need_sample, need_topk=need_topk,
                    rowwise=True,
                )
                counts = counts + active.astype(counts.dtype)
                nxt, active, budget = advance_stops(
                    nxt, active, budget, eos, cache["len"], eng_max_len
                )
                return (nxt, cache, active, budget, counts), nxt

            (tok, cache, active, budget, counts), block = jax.lax.scan(
                body, (tok, cache, active, budget, counts), None, length=k
            )
            return block, tok, cache

        def _prefill(params, tokens, lens, lanes, cache, temps, topks, key,
                     uids, counts, need_sample, need_topk):
            # one jitted call per (bucket_len, group_size): forward the whole
            # padded group, write each row's cache into its lane through the
            # layout, and sample each row's first token at position len-1
            # under that row's (uid, token-index) key
            logits_all, _, produced = model.forward(
                params, {"tokens": tokens}, remat=False, want_cache=True
            )
            idx = jnp.clip(lens - 1, 0, tokens.shape[1] - 1)
            logits = jnp.take_along_axis(logits_all, idx[:, None, None], axis=1)[:, 0]
            cache = model.write_prefill(cache, produced, lanes, lens, layout)
            first = sample_tokens(
                logits, temps, topks, request_keys(key, uids, counts),
                need_sample=need_sample, need_topk=need_topk, rowwise=True,
            )
            return first, cache

        def _dloop(params, cache, dstate, key, k_max, need_sample, need_topk):
            # device-resident scheduler: one while-loop iteration is one
            # decode step for every live lane — feeding lanes consume their
            # staged prompt token-by-token, drained lanes sample — followed
            # by at most one dead-lane refill from the staged ring.  The
            # loop exits on the step bound, on a freeze the refill did not
            # cover (the host must schedule), or when nothing is live and
            # nothing is staged.  The host reads back only (block, steps,
            # consumed_lane, consumed_step); the scheduler state chains
            # device-side between dispatches and is rebuilt from host
            # bookkeeping at every cycle boundary.  The state crosses the
            # jit boundary *packed* — same-dtype lane/ring vectors stacked
            # into a few matrices — so a cycle pays a handful of host→
            # device transfers instead of ~25; rows unpack here at trace
            # time for free.
            B, S, Q = n_lanes, eng_max_len, n_staged
            li = dstate["lanes_i"]
            ring = dstate["ring_i"]
            s_len, s_uid, s_count0 = ring[0], ring[1], ring[2]
            s_topks, s_eos, s_budget = ring[3], ring[4], ring[5]
            s_temps, s_tokens = dstate["s_temps"], dstate["s_tokens"]
            s_avail = dstate["scal"][1]

            def cond(c):
                more = jnp.any(c["live"]) | (c["s_next"] < s_avail)
                return (c["t"] < k_max) & more & ~c["stall"]

            def body(c):
                t = c["t"]
                cache = c["cache"]
                live, occupied = c["live"], c["occupied"]
                pend, fed, feed_buf = c["pend"], c["fed"], c["feed_buf"]
                feeding = pend > 0
                feed = jnp.where(
                    feeding,
                    feed_buf[jnp.arange(B), jnp.clip(fed, 0, S - 1)],
                    c["tok"],
                )
                len_prev = cache["len"]
                logits, cache = model.decode_step(params, feed, cache, layout)
                cache["len"] = jnp.where(
                    live, cache["len"], jnp.where(occupied, len_prev, 0)
                )
                pend = jnp.where(feeding, pend - 1, pend)
                fed = fed + feeding.astype(fed.dtype)
                # a lane samples the step its prompt drains — the feed of
                # the last prompt token doubles as the first-token forward
                sample_now = live & (pend == 0)
                keys = request_keys(key, c["uids"], c["counts"])
                nxt = sample_tokens(
                    logits, c["temps"], c["topks"], keys,
                    need_sample=need_sample, need_topk=need_topk,
                    rowwise=True,
                )
                counts = c["counts"] + sample_now.astype(c["counts"].dtype)
                tokens_out, act_out, budget = advance_stops(
                    nxt, sample_now, c["budget"], c["eos"], cache["len"],
                    eng_max_len,
                )
                tok = jnp.where(sample_now, tokens_out, c["tok"])
                nf = sample_now & ~act_out  # newly frozen lanes
                live = act_out | (pend > 0)
                occupied = occupied | nf
                block = c["block"].at[t].set(tokens_out)
                # at most one refill per iteration: swap the first dead
                # lane for the next staged request, entirely on device
                free = ~live
                do = (c["s_next"] < s_avail) & jnp.any(free)
                lane = jnp.argmax(free).astype(jnp.int32)
                row = jnp.clip(c["s_next"], 0, Q - 1)
                lm = (jnp.arange(B) == lane) & do
                uids = jnp.where(lm, s_uid[row], c["uids"])
                temps = jnp.where(lm, s_temps[row], c["temps"])
                topks = jnp.where(lm, s_topks[row], c["topks"])
                eos = jnp.where(lm, s_eos[row], c["eos"])
                budget = jnp.where(lm, s_budget[row], budget)
                counts = jnp.where(lm, s_count0[row], counts)
                pend = jnp.where(lm, s_len[row], pend)
                fed = jnp.where(lm, 0, fed)
                feed_buf = jnp.where(
                    lm[:, None], s_tokens[row][None, :], feed_buf
                )
                cache["len"] = jnp.where(lm, 0, cache["len"])
                tbl = cache.get("tables")
                if tbl is not None and "s_tbl_full" in dstate and "full" in tbl:
                    tbl["full"] = jnp.where(
                        lm[:, None], dstate["s_tbl_full"][row][None, :],
                        tbl["full"],
                    )
                if tbl is not None and "s_tbl_win" in dstate and "win" in tbl:
                    tbl["win"] = jnp.where(
                        lm[:, None], dstate["s_tbl_win"][row][None, :],
                        tbl["win"],
                    )
                cache = reset_lanes(model.cfg, cache, lm)
                live = live | lm
                occupied = occupied | lm
                consumed_lane = jnp.where(
                    do, c["consumed_lane"].at[row].set(lane),
                    c["consumed_lane"],
                )
                consumed_step = jnp.where(
                    do, c["consumed_step"].at[row].set(t),
                    c["consumed_step"],
                )
                s_next = c["s_next"] + do.astype(c["s_next"].dtype)
                # a freeze the refill did not cover stalls the loop: the
                # host has to admit / restage at the next cycle boundary
                stall = c["stall"] | jnp.any(nf & ~lm)
                return {
                    "t": t + 1, "tok": tok, "cache": cache, "live": live,
                    "occupied": occupied, "pend": pend, "fed": fed,
                    "counts": counts, "budget": budget, "uids": uids,
                    "temps": temps, "topks": topks, "eos": eos,
                    "feed_buf": feed_buf, "s_next": s_next, "stall": stall,
                    "block": block, "consumed_lane": consumed_lane,
                    "consumed_step": consumed_step,
                }

            init = {
                "t": jnp.asarray(0, jnp.int32),
                "tok": li[0], "cache": cache,
                "live": li[1].astype(bool), "occupied": li[2].astype(bool),
                "pend": li[3], "fed": li[4],
                "counts": li[5], "budget": li[6],
                "uids": li[7], "temps": dstate["temps"],
                "topks": li[8], "eos": li[9],
                "feed_buf": dstate["feed_buf"],
                "s_next": dstate["scal"][0],
                "stall": jnp.asarray(False),
                "block": jnp.zeros((k_max, B), jnp.int32),
                "consumed_lane": jnp.full((Q,), -1, jnp.int32),
                "consumed_step": jnp.full((Q,), -1, jnp.int32),
            }
            f = jax.lax.while_loop(cond, body, init)
            dstate = dict(dstate)
            dstate["lanes_i"] = jnp.stack(
                [f["tok"], f["live"].astype(jnp.int32),
                 f["occupied"].astype(jnp.int32), f["pend"], f["fed"],
                 f["counts"], f["budget"], f["uids"], f["topks"], f["eos"]]
            )
            dstate["temps"] = f["temps"]
            dstate["feed_buf"] = f["feed_buf"]
            dstate["scal"] = jnp.stack([f["s_next"], s_avail])
            return (f["block"], f["t"], f["consumed_lane"],
                    f["consumed_step"], dstate, f["cache"])

        def _chunk(params, tokens, cache, lanes, starts, lengths):
            # one dispatch absorbs a chunk of every currently-chunking lane
            return model.prefill_chunk(
                params, tokens, cache, lanes, starts, lengths, layout
            )

        def _sdraft(dparams, tok, cache, temps, topks, gi, keep, key, uids,
                    counts, g, need_sample, need_topk):
            # speculative draft scan: the fused-decode body re-run under
            # the drafter tree with per-lane step masks — lane i proposes
            # only its first gi[i] steps (gi = 0 freezes it; it still gets
            # the verify pass's bonus token).  Proposals are NOT
            # commitments: cache["len"] rewinds to the round's start so
            # the verify chunk rescores (and rewrites at verifier
            # fidelity) every drafted position.  Draft keys live on their
            # own fold_in stream, independent of the verify pass's
            # accept/residual draws.
            len0 = cache["len"]
            dkey = jax.random.fold_in(key, 1)

            def body(carry, t):
                tok, cache, counts = carry
                len_prev = cache["len"]
                drafting = t < gi
                logits, cache = model.decode_step(dparams, tok, cache, layout)
                cache["len"] = jnp.where(
                    drafting, cache["len"], jnp.where(keep, len_prev, 0)
                )
                keys = request_keys(dkey, uids, counts)
                nxt = sample_tokens(
                    logits, temps, topks, keys,
                    need_sample=need_sample, need_topk=need_topk,
                    rowwise=True,
                )
                nxt = jnp.where(drafting, nxt, 0)
                if need_sample:
                    # the drafter's post-filter distribution at each
                    # proposal, for the rejection rule; zeroed past gi so
                    # the residual at the bonus slot is the verifier's
                    # own distribution
                    probs = filtered_probs(
                        logits, temps, topks, need_topk=need_topk
                    )
                    probs = jnp.where(drafting[:, None], probs, 0.0)
                else:
                    probs = jnp.zeros((n_lanes, 1), jnp.float32)
                counts = counts + drafting.astype(counts.dtype)
                tok = jnp.where(drafting, nxt, tok)
                return (tok, cache, counts), (nxt, probs)

            (_, cache, _), (drafts, dprobs) = jax.lax.scan(
                body, (tok, cache, counts), jnp.arange(g)
            )
            cache["len"] = jnp.where(keep, len0, 0)
            return drafts, dprobs, cache

        def _sverify(vparams, tok, drafts, dprobs, cache, temps, topks,
                     active, key, uids, counts, gi, g, need_sample,
                     need_topk):
            # speculative verify: ONE chunked-prefill dispatch through the
            # verifier scores all gamma+1 positions — row i feeds its last
            # committed token plus its drafts at starts = the committed
            # length, (re)writing verifier-fidelity KV over every draft
            # slot while all_logits=True unembeds the whole chunk.  Slot j
            # of the logits is the verifier distribution for the token
            # AFTER input j, so the accept rule, the trailing
            # correction/bonus token, and the committed-length rewind all
            # resolve on device; the host fetches only (block, n_acc).
            b = n_lanes
            len0 = cache["len"]
            rows = jnp.concatenate([tok[:, None], drafts.T], axis=1)
            lanes = jnp.where(active, jnp.arange(b), b).astype(jnp.int32)
            lengths = jnp.where(active, gi + 1, 0).astype(jnp.int32)
            logits_all, cache = model.prefill_chunk(
                vparams, rows, cache, lanes, len0.astype(jnp.int32),
                lengths, layout, all_logits=True,
            )
            tb = jnp.broadcast_to(temps[:, None], (b, g + 1))
            kb = jnp.broadcast_to(topks[:, None], (b, g + 1))
            p_ver = filtered_probs(logits_all, tb, kb, need_topk=need_topk)
            akeys = request_keys(jax.random.fold_in(key, 2), uids, counts)
            rkeys = request_keys(jax.random.fold_in(key, 3), uids, counts)
            block, n_acc = spec_accept(
                drafts.T, jnp.moveaxis(dprobs, 0, 1), p_ver, gi,
                akeys, rkeys, need_sample=need_sample,
            )
            block = jnp.where(active[:, None], block, 0)
            n_acc = jnp.where(active, n_acc, 0)
            # device half of the rollback: committed length = accepted
            # prefix + the trailing emitted token (whose KV is written
            # next round, like any freshly sampled token); stale draft KV
            # past it is dead under the length masks.  prefill_chunk
            # advanced active lanes to len0 + gi + 1 — rewind them.
            cache["len"] = jnp.where(active, len0 + n_acc + 1, cache["len"])
            last = jnp.take_along_axis(block, n_acc[:, None], axis=1)[:, 0]
            tok = jnp.where(active, last, tok)
            return block, n_acc, tok, cache

        # the need_* flags are static so all-greedy batches compile to a
        # bare argmax (no vocab sort / categorical in the decode hot path);
        # at most 4 _decode variants exist, warmed untimed on first use.
        # donate_argnums hands the cache (and the decode's token buffer) to
        # XLA for in-place update — without it every dispatch copies the
        # whole pool because the engine reuses the input cache.
        jit_kw: dict = {"decode": {}, "prefill": {}, "chunk": {},
                        "dloop": {}, "sdraft": {}, "sverify": {}}
        if self._shardings is not None:
            # pin explicit in/out shardings on every executable: params TP,
            # cache seq/pages-sharded, per-lane vectors over DP, prefill /
            # chunk row batches replicated (they scatter into the sharded
            # cache), rng keys replicated.  The device scheduler's state
            # dict is all scheduling metadata (a few KB) — replicated via
            # a prefix sharding rather than lane-split for simplicity.
            from jax.sharding import NamedSharding, PartitionSpec as _P

            psh = self._shardings["params"]
            csh = self._shardings["cache"]
            lane = self._shardings["lane"]
            repl = self._shardings["repl"]
            blk = NamedSharding(mesh, _P(None, *tuple(lane.spec)))
            jit_kw["decode"] = dict(
                in_shardings=(psh, lane, csh, lane, lane, lane, lane, repl,
                              lane, lane, lane, lane),
                out_shardings=(blk, lane, csh),
            )
            jit_kw["prefill"] = dict(
                in_shardings=(psh, repl, repl, repl, csh, repl, repl, repl,
                              repl, repl),
                out_shardings=(repl, csh),
            )
            jit_kw["chunk"] = dict(
                in_shardings=(psh, repl, csh, repl, repl, repl),
                out_shardings=(repl, csh),
            )
            jit_kw["dloop"] = dict(
                in_shardings=(psh, csh, repl, repl),
                out_shardings=(repl, repl, repl, repl, repl, csh),
            )
            if self._spec:
                # drafter params live under their own sharding map; the
                # per-step draft probs carry a trailing vocab axis (kept
                # unsharded — only read back through the verify pass)
                prb = NamedSharding(mesh, _P(None, *tuple(lane.spec), None))
                rowsh = NamedSharding(mesh, _P(*tuple(lane.spec), None))
                psh_d = self._shardings["draft_params"]
                jit_kw["sdraft"] = dict(
                    in_shardings=(psh_d, lane, csh, lane, lane, lane, lane,
                                  repl, lane, lane),
                    out_shardings=(blk, prb, csh),
                )
                jit_kw["sverify"] = dict(
                    in_shardings=(psh, lane, blk, prb, csh, lane, lane,
                                  lane, repl, lane, lane, lane),
                    out_shardings=(rowsh, lane, lane, csh),
                )
        # statics are passed *positionally* (static_argnums): pjit rejects
        # kwargs outright once in_shardings is specified
        self._decode = jax.jit(
            _decode,
            static_argnums=(12, 13, 14),  # k, need_sample, need_topk
            donate_argnums=(1, 2) if donate else (),
            **jit_kw["decode"],
        )
        self._prefill = jax.jit(
            _prefill,
            static_argnums=(10, 11),  # need_sample, need_topk
            donate_argnums=(4,) if donate else (),
            **jit_kw["prefill"],
        )
        self._chunk = jax.jit(
            _chunk, donate_argnums=(2,) if donate else (), **jit_kw["chunk"]
        )
        self._dloop = jax.jit(
            _dloop,
            static_argnums=(4, 5, 6),  # k_max, need_sample, need_topk
            donate_argnums=(1, 2) if donate else (),
            **jit_kw["dloop"],
        )
        if self._spec:
            # _sdraft keeps tok alive (the verify pass needs it as the
            # chunk's first row), so only the cache is donated; _sverify
            # consumes both tok and the drafted cache.
            self._sdraft = jax.jit(
                _sdraft,
                static_argnums=(10, 11, 12),  # g, need_sample, need_topk
                donate_argnums=(2,) if donate else (),
                **jit_kw["sdraft"],
            )
            self._sverify = jax.jit(
                _sverify,
                static_argnums=(12, 13, 14),  # g, need_sample, need_topk
                donate_argnums=(1, 4) if donate else (),
                **jit_kw["sverify"],
            )
        self._warmed: set[tuple] = set()

    # -- request intake ------------------------------------------------------

    def submit(
        self, prompt: Sequence[int], sampling: Optional[SamplingParams] = None
    ) -> int:
        """Enqueue a request; returns its uid."""
        prompt = [int(t) for t in prompt]
        sampling = sampling or SamplingParams()
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) >= self.max_len:
            raise ValueError(
                f"prompt length {len(prompt)} >= cache capacity {self.max_len}"
            )
        if self.pool is not None:
            cap = min(len(prompt) + sampling.max_new_tokens, self.max_len)
            need = self.pool.pages_for_request(cap)
            if need > self.pool.layout.num_pages:
                raise ValueError(
                    f"request needs up to {need} pages but the pool has only "
                    f"{self.pool.layout.num_pages}; raise --num-pages or "
                    "lower max_new_tokens"
                )
        uid = self._next_uid
        self._next_uid += 1
        self.queue.append(_Request(uid, prompt, sampling))
        return uid

    # -- scheduling ----------------------------------------------------------

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def _finish(self, i: int, reason: str, out: list[GenerationResult]) -> None:
        s = self.slots[i]
        out.append(GenerationResult(s.uid, s.prompt, s.generated, reason))
        self.tokens_generated += len(s.generated)
        self.slots[i] = None
        self._slots_dirty = True
        self._last_emit.pop(s.uid, None)
        if self.pool is not None:
            self.pool.release(i)

    def _absorb(
        self, i: int, token: int, out: list[GenerationResult], *,
        from_decode: bool = False,
    ) -> None:
        """Record a freshly sampled token for slot i; finish on a stop.

        These rules are mirrored on device by ``sampling.advance_stops``
        (the K-step scan's freeze logic) — keep the two in lockstep."""
        s = self.slots[i]
        sp = s.sampling
        if sp.eos_id >= 0 and token == sp.eos_id:
            self._finish(i, "eos", out)
            return
        s.generated.append(token)
        now = time.perf_counter()
        last = self._last_emit.get(s.uid)
        if last is not None:
            self._itl_ms.append((now - last) * 1e3)
        self._last_emit[s.uid] = now
        if from_decode:
            self.decode_tokens += 1
        if len(s.generated) >= sp.max_new_tokens:
            self._finish(i, "length", out)
        elif len(s.prompt) + len(s.generated) >= self.max_len:
            # the request hit its logical capacity (page-table width /
            # slab length) — distinct from pool pressure, which preempts
            self._finish(i, "cache_full", out)

    def _preempt(self, i: int, out: list[GenerationResult]) -> None:
        """Evict lane i: free its pages, requeue it with a resume prefix."""
        s = self.slots[i]
        self.slots[i] = None
        self._slots_dirty = True
        self.pool.release(i)
        self.preemptions += 1
        self.queue.appendleft(
            _Request(s.uid, s.prompt, s.sampling, prefix=list(s.generated))
        )

    def _bucket(self, n: int) -> int:
        if self._exact_prefill:
            return n
        for b in self.prefill_buckets:
            if b >= n:
                return b
        return self.prefill_buckets[-1]

    def _admit(self, out: list[GenerationResult]) -> None:
        """Move queued requests into lanes; one batched prefill per bucket.

        Prompts longer than ``prefill_chunk`` take the chunked route: the
        lane is claimed (and its pages reserved) now, but the prompt is
        absorbed chunk-by-chunk across the following scheduling steps.

        With a prefix index, admission first asks it for the longest
        cached prefix (page granularity): the hit pages are mapped shared
        into the lane's table and only the uncached tail is absorbed —
        through the chunked machinery, since a prefix-hit lane is exactly
        a lane that already absorbed its first chunks."""
        picked: list[tuple[_Request, int, int]] = []
        n_taken = 0
        while self.queue and n_taken < self.max_prefill_batch:
            i = self._free_slot()
            if i is None:
                break
            req = self.queue[0]
            seq = list(req.prompt) + list(req.prefix)
            length = len(seq)
            chunked = (
                self.prefill_chunk is not None and length > self.prefill_chunk
            )
            # a windowed chunked admission defers its window-ring mapping:
            # the ring slots are claimed chunk-by-chunk (_advance_chunks)
            # as the window slides over the prompt
            defer = chunked and self._win_chunk
            shared_len, shared_pids = 0, ()
            if self._prefix is not None:
                shared_len, shared_pids = self._prefix.match(seq)
            if self.pool is not None:
                ok = self.pool.alloc_prefill(
                    i, length, shared_full=shared_pids, shared_len=shared_len,
                    defer_win=defer,
                )
                # pool pressure: shed LRU index entries before giving up —
                # each evict() can invalidate matched pages, so re-match
                while (
                    not ok
                    and self._prefix is not None
                    and self._prefix.evict(1)
                ):
                    shared_len, shared_pids = self._prefix.match(seq)
                    ok = self.pool.alloc_prefill(
                        i, length, shared_full=shared_pids,
                        shared_len=shared_len, defer_win=defer,
                    )
                if not ok:
                    break  # retry next step, after frees/preemptions
            self.queue.popleft()
            n_taken += 1
            if shared_len > 0:
                # prefix hit: absorb only the uncached tail, chunk-wise
                self.prefix_hits += 1
                self.prefix_hit_tokens += shared_len
                self.slots[i] = _Slot(
                    req, pos=shared_len, seq=self._admit_seq,
                    pending=seq[shared_len:],
                )
                self._admit_seq += 1
                self.admitted += 1
                self._slots_dirty = True
                continue
            if chunked:
                self.slots[i] = _Slot(
                    req, pos=0, seq=self._admit_seq,
                    pending=seq,
                )
                self._admit_seq += 1
                self.admitted += 1
                self._slots_dirty = True
                continue
            self.slots[i] = _Slot(req, pos=length, seq=self._admit_seq)
            self._admit_seq += 1
            self._slots_dirty = True
            picked.append((req, i, length))
        if not picked:
            return
        groups: dict[int, list[tuple[_Request, int, int]]] = {}
        for item in picked:
            groups.setdefault(self._bucket(item[2]), []).append(item)
        for lb in sorted(groups):
            self._prefill_group(lb, groups[lb], out)

    def _prefill_group(
        self, lb: int, items: list[tuple[_Request, int, int]],
        out: list[GenerationResult],
    ) -> None:
        nb = _next_pow2(len(items))
        tokens = np.zeros((nb, lb), np.int32)
        lens = np.zeros((nb,), np.int32)
        lanes = np.full((nb,), self.max_batch, np.int32)  # sentinel = pad row
        temps = np.zeros((nb,), np.float32)
        topks = np.zeros((nb,), np.int32)
        uids = np.zeros((nb,), np.int32)
        counts = np.zeros((nb,), np.int32)
        for r, (req, i, length) in enumerate(items):
            tokens[r, :length] = req.prompt + req.prefix
            lens[r] = length
            lanes[r] = i
            temps[r] = req.sampling.temperature
            topks[r] = req.sampling.top_k
            uids[r] = req.uid
            # first sampled token's index: resume prefixes already hold
            # the request's first len(prefix) generated tokens
            counts[r] = len(req.prefix)
        need_sample = any(req.sampling.temperature > 0 for req, _, _ in items)
        need_topk = any(req.sampling.top_k > 0 for req, _, _ in items)
        if self.pool is not None:
            if self.pool.pending_copies:
                self.cache = self.pool.apply_pending(self.cache)
            dt = self.pool.device_tables()
            if dt:  # ssm-only paged archs have no table'd layers
                self.cache["tables"] = dt
        with self._kernel_ctx(), _quiet_donation():
            first, self.cache = self._prefill(
                self.params, jnp.asarray(tokens), jnp.asarray(lens),
                jnp.asarray(lanes), self.cache, jnp.asarray(temps),
                jnp.asarray(topks), self.key, jnp.asarray(uids),
                jnp.asarray(counts), need_sample, need_topk,
            )
        if self.pool is not None:
            # the donated call consumed the table buffers the pool held;
            # re-anchor its incremental sync on the returned arrays
            self.pool.adopt_tables(self.cache.get("tables"))
        self.tokens = self.tokens.at[lanes].set(first, mode="drop")
        self.prefill_batches += 1
        host_first = np.asarray(first)
        if self._prefix is not None:
            # index the freshly written pages while the lane still maps
            # them (_absorb may finish the lane and release its claim;
            # the index's own references keep the KV resident)
            for req, i, length in items:
                full, tail = self.pool.prompt_pages(i, length)
                self._prefix.insert(
                    req.prompt + req.prefix, full, tail,
                    length % self.pool.layout.page_size,
                )
        for r, (req, i, _) in enumerate(items):
            self.admitted += 1
            self._absorb(i, int(host_first[r]), out)

    def _advance_chunks(self, out: list[GenerationResult]) -> None:
        """One prompt chunk of *every* chunk-prefilling lane per scheduling
        step, absorbed by a single batched dispatch (rows padded to a power
        of two with sentinel lanes, so the executable retraces O(log B)
        times, not per lane count).  Previously each chunking lane cost its
        own dispatch per step.

        A lane's final chunk's logits seed its request's first sampled
        token, so a lane never idles fully-prefilled-but-unsampled across a
        dispatch.
        """
        # prefix-hit lanes drain their uncached tail here even when chunked
        # prefill proper is off — _tail_chunk covers that case.  Refill-fed
        # lanes (s.feed) drain on device instead, never through this path.
        csz = self.prefill_chunk or self._tail_chunk
        chunking = [
            i for i, s in enumerate(self.slots)
            if s is not None and s.pending and not s.feed
        ]
        if not chunking:
            return
        if self._win_chunk and self.pool is not None:
            # windowed chunk writes walk the window ring: claim this
            # chunk's ring slots now (full pages were mapped whole at
            # admission; alloc_prefill deferred the ring).  Pool pressure
            # preempts youngest-first, like the decode runway reservation.
            for i in list(chunking):
                s = self.slots[i]
                if s is None:
                    continue
                k = min(csz, len(s.pending))
                while self.slots[i] is not None and not self.pool.ensure_steps(
                    i, self.slots[i].pos, k
                ):
                    victim = max(
                        (j for j, t_ in enumerate(self.slots)
                         if t_ is not None),
                        key=lambda j: self.slots[j].seq,
                    )
                    self._preempt(victim, out)
                    if victim == i:
                        break
            chunking = [i for i in chunking if self.slots[i] is not None]
            if not chunking:
                return
        nb = _next_pow2(len(chunking))
        toks = np.zeros((nb, csz), np.int32)
        lanes = np.full((nb,), self.max_batch, np.int32)  # sentinel = pad row
        starts = np.zeros((nb,), np.int32)
        lengths = np.zeros((nb,), np.int32)
        for r, i in enumerate(chunking):
            s = self.slots[i]
            part = s.pending[:csz]
            toks[r, : len(part)] = part
            lanes[r] = i
            starts[r] = s.pos
            lengths[r] = len(part)
        if self.pool is not None:
            if self.pool.pending_copies:
                self.cache = self.pool.apply_pending(self.cache)
            dt = self.pool.device_tables()
            if dt:  # ssm-only paged archs have no table'd layers
                self.cache["tables"] = dt
        with self._kernel_ctx(), _quiet_donation():
            logits, self.cache = self._chunk(
                self.params, jnp.asarray(toks), self.cache,
                jnp.asarray(lanes), jnp.asarray(starts), jnp.asarray(lengths),
            )
        if self.pool is not None:
            self.pool.adopt_tables(self.cache.get("tables"))
        self.prefill_chunks += 1
        finishing: list[tuple[int, int]] = []  # (row, lane)
        for r, i in enumerate(chunking):
            s = self.slots[i]
            took = int(lengths[r])
            s.pos += took
            s.pending = s.pending[took:]
            if not s.pending:
                finishing.append((r, i))
        if finishing and self._prefix is not None:
            # the lane's whole prompt(+resume prefix) is now cached: index
            # its pages before _absorb can finish/release the lane
            for _, i in finishing:
                s = self.slots[i]
                full, tail = self.pool.prompt_pages(i, s.pos)
                self._prefix.insert(
                    s.prompt + s.generated, full, tail,
                    s.pos % self.pool.layout.page_size,
                )
        if finishing:
            temps = np.zeros((nb,), np.float32)
            topks = np.zeros((nb,), np.int32)
            uids = np.zeros((nb,), np.int32)
            counts = np.zeros((nb,), np.int32)
            for r, i in finishing:
                s = self.slots[i]
                temps[r] = s.sampling.temperature
                topks[r] = s.sampling.top_k
                uids[r] = s.uid
                counts[r] = len(s.generated)
            keys = request_keys(
                self.key, jnp.asarray(uids), jnp.asarray(counts)
            )
            first = sample_tokens(
                logits, jnp.asarray(temps), jnp.asarray(topks), keys,
                need_sample=bool((temps > 0).any()),
                need_topk=bool((topks > 0).any()),
                rowwise=True,
            )
            host_first = np.asarray(first)
            for r, i in finishing:
                self.tokens = self.tokens.at[i].set(first[r])
                self._slots_dirty = True
                self._absorb(i, int(host_first[r]), out)

    def _ensure_capacity(self, out: list[GenerationResult]) -> None:
        """Back every decoding lane's next K writes; preempt on pressure.

        Lanes are served oldest-first and victims chosen youngest-first, so
        the oldest request always makes progress (a request that could
        never fit alone is rejected at submit).  Reserving the whole
        dispatch up front (``ensure_steps``) is what rules out mid-scan
        pool exhaustion."""
        if self.pool is None:
            return
        order = sorted(
            (
                i for i, s in enumerate(self.slots)
                if s is not None and (not s.pending or s.feed)
            ),
            key=lambda i: self.slots[i].seq,
        )
        for i in order:
            s = self.slots[i]
            if s is None:  # already evicted as an earlier lane's victim
                continue
            # a lane whose remaining token budget is < the horizon freezes
            # on device before the loop ends — don't reserve (and
            # potentially preempt someone for) pages its writes will never
            # reach.  Refill-fed lanes also write their still-pending
            # prompt tokens; every lane stops at the logical capacity.
            k = max(
                1,
                min(
                    self._horizon,
                    len(s.pending)
                    + max(1, s.sampling.max_new_tokens - len(s.generated)),
                    self.max_len - s.pos,
                ),
            )
            while self.slots[i] is not None and not self.pool.ensure_steps(
                i, self.slots[i].pos, k
            ):
                # cached-but-idle prefix pages are cheaper to give up than
                # a live lane: shed LRU index entries before preempting
                if self._prefix is not None and self._prefix.evict(1):
                    continue
                victim = max(
                    (j for j, t in enumerate(self.slots) if t is not None),
                    key=lambda j: self.slots[j].seq,
                )
                self._preempt(victim, out)
                if victim == i:
                    break

    def _slot_consts(self) -> dict:
        """Per-lane device constants, rebuilt only when the slot set changes
        (not per dispatch — the per-step rebuild was pure host overhead)."""
        if not self._slots_dirty and self._consts is not None:
            return self._consts
        decode = [s is not None and not s.pending for s in self.slots]
        keep = [s is not None for s in self.slots]
        self._consts = {
            "active_np": np.array(decode),
            "active": jnp.asarray(np.array(decode)),
            "keep": jnp.asarray(np.array(keep)),
            "temps": jnp.asarray(
                [
                    s.sampling.temperature if (s and not s.pending) else 0.0
                    for s in self.slots
                ],
                jnp.float32,
            ),
            "topks": jnp.asarray(
                [
                    s.sampling.top_k if (s and not s.pending) else 0
                    for s in self.slots
                ],
                jnp.int32,
            ),
            "eos": jnp.asarray(
                [
                    s.sampling.eos_id if (s and not s.pending) else -1
                    for s in self.slots
                ],
                jnp.int32,
            ),
            "uids": jnp.asarray(
                [s.uid if s else 0 for s in self.slots], jnp.int32
            ),
            "need_sample": any(
                s is not None and not s.pending and s.sampling.temperature > 0
                for s in self.slots
            ),
            "need_topk": any(
                s is not None and not s.pending and s.sampling.top_k > 0
                for s in self.slots
            ),
        }
        self._slots_dirty = False
        return self._consts

    def step(self) -> list[GenerationResult]:
        """One scheduling step: admit what fits, advance chunked prefills,
        run one decode dispatch (fixed-K scan) or one device-scheduler
        cycle (run-until-stop while-loops); return finished requests."""
        if self._spec:
            return self._step_spec()
        if self._device:
            return self._step_device()
        out: list[GenerationResult] = []
        self._admit(out)
        if self.prefill_chunk is not None or self._prefix is not None:
            self._advance_chunks(out)
        t_prefill_done = time.perf_counter()
        self._ensure_capacity(out)
        consts = self._slot_consts()
        active = consts["active_np"]
        self.max_concurrency = max(self.max_concurrency, int(active.sum()))
        if not active.any():
            return out
        self._util_sum += self._cache_utilization()
        self._util_n += 1
        self._kv_bytes_sum += self._live_kv_bytes()
        if self.pool is not None:
            if self.pool.pending_copies:
                self.cache = self.pool.apply_pending(self.cache)
            dt = self.pool.device_tables()
            if dt:  # ssm-only paged archs have no table'd layers
                self.cache["tables"] = dt
        k = self.steps_per_dispatch
        budget = np.zeros((self.max_batch,), np.int32)
        counts = np.zeros((self.max_batch,), np.int32)
        for i, s in enumerate(self.slots):
            if s is not None and not s.pending:
                budget[i] = s.sampling.max_new_tokens - len(s.generated)
                counts[i] = len(s.generated)
        args = (
            self.params, self.tokens, self.cache, consts["temps"],
            consts["topks"], consts["active"], consts["keep"], self.key,
            consts["eos"], jnp.asarray(budget), consts["uids"],
            jnp.asarray(counts),
        )
        sig = (k, consts["need_sample"], consts["need_topk"])
        t_sched = time.perf_counter()  # warmup compile time is not host overhead
        if ("decode",) + sig not in self._warmed:
            # untimed warmup: trace+compile of this variant must not land in
            # decode_wall_s (it would dominate ms_per_decode_step on short
            # runs).  The warmup runs on *copies* of the donated operands so
            # the originals stay valid for the timed call, whose result is
            # the one absorbed.
            wargs = args
            if self.donate:
                tok_c, cache_c = jax.tree_util.tree_map(
                    jnp.copy, (args[1], args[2])
                )
                wargs = (args[0], tok_c, cache_c) + args[3:]
            with self._kernel_ctx(), _quiet_donation():
                jax.block_until_ready(self._decode(*wargs, *sig))
            self._warmed.add(("decode",) + sig)
        t0 = time.perf_counter()
        with self._kernel_ctx(), _quiet_donation():
            block, tok, self.cache = self._decode(*args, *sig)
            tok.block_until_ready()
        t1 = time.perf_counter()
        self.decode_wall_s += t1 - t0
        self.decode_steps += k
        self.dispatches += 1
        self.tokens = tok
        if self.pool is not None:
            self.pool.adopt_tables(self.cache.get("tables"))
        host_block = self._fetch_block(block)  # (K, B): one sync per K tokens
        self.block_fetches += 1
        live = [i for i in range(self.max_batch) if active[i]]
        for t in range(k):
            for i in list(live):
                self.slots[i].pos += 1  # mirror cache["len"] advancing
            for i in list(live):
                self._absorb(i, int(host_block[t, i]), out, from_decode=True)
                if self.slots[i] is None:
                    live.remove(i)
        t_end = time.perf_counter()
        self.sched_host_s += (t_sched - t_prefill_done) + (t_end - t1)
        return out

    # -- speculative decoding ------------------------------------------------

    @staticmethod
    def pick_spec_gamma(draft_bytes: int, verify_bytes: int, *,
                        alpha: float = 0.75, g_max: int = 16) -> int:
        """Roofline choice of the draft length for ``spec_gamma="auto"``.

        A round moves ``g * draft_bytes`` (one drafter sweep per proposed
        token) plus ``verify_bytes`` (one verifier sweep scores all g+1
        positions) and commits ``E[gain] = (1 - alpha^(g+1)) / (1 - alpha)``
        tokens under an i.i.d. per-token acceptance rate ``alpha`` (the
        standard speculative-decoding progress model).  Minimising bytes
        per accepted token balances drafter cheapness against wasted work
        on rejection; alpha defaults to 0.75, a typical magnitude-pruned
        drafter's agreement with its dense parent.
        """
        best_g, best_cost = 1, float("inf")
        for g in range(1, g_max + 1):
            if alpha >= 1.0:
                exp_tok = float(g + 1)
            else:
                exp_tok = (1.0 - alpha ** (g + 1)) / (1.0 - alpha)
            cost = (g * draft_bytes + verify_bytes) / exp_tok
            if cost < best_cost:
                best_g, best_cost = g, cost
        return best_g

    def _step_spec(self) -> list[GenerationResult]:
        """One speculative round: gamma drafter decode steps (one fused
        scan dispatch) chained device-side into one verifier chunk
        dispatch; the host syncs ONCE per round, on the accepted block.
        Emits between 1 and gamma+1 tokens per live lane — output
        distributions are exactly the verifier's (longest-prefix accept
        under greedy, rejection sampling otherwise)."""
        out: list[GenerationResult] = []
        self._admit(out)
        if self.prefill_chunk is not None or self._prefix is not None:
            self._advance_chunks(out)
        t_prefill_done = time.perf_counter()
        self._ensure_capacity(out)  # horizon covers gamma+1 writes
        consts = self._slot_consts()
        active = consts["active_np"]
        self.max_concurrency = max(self.max_concurrency, int(active.sum()))
        if not active.any():
            return out
        self._util_sum += self._cache_utilization()
        self._util_n += 1
        self._kv_bytes_sum += self._live_kv_bytes()
        if self.pool is not None:
            if self.pool.pending_copies:
                self.cache = self.pool.apply_pending(self.cache)
            dt = self.pool.device_tables()
            if dt:
                self.cache["tables"] = dt
        g = self.spec_gamma
        counts = np.zeros((self.max_batch,), np.int32)
        gi = np.zeros((self.max_batch,), np.int32)
        for i, s in enumerate(self.slots):
            if s is not None and not s.pending and active[i]:
                counts[i] = len(s.generated)
                rem = s.sampling.max_new_tokens - len(s.generated)
                # leave room for the verify pass's guaranteed token: a
                # lane with 1 token of budget or cache left drafts nothing
                # and still finishes via the bonus
                gi[i] = max(0, min(g, self.max_len - 1 - s.pos, rem - 1))
        gi_j = jnp.asarray(gi)
        counts_j = jnp.asarray(counts)
        sig = (g, consts["need_sample"], consts["need_topk"])
        draft_args = (
            self._draft_params, self.tokens, self.cache, consts["temps"],
            consts["topks"], gi_j, consts["keep"], self.key,
            consts["uids"], counts_j,
        )
        t_sched = time.perf_counter()
        if ("spec",) + sig not in self._warmed:
            # untimed warmup of both executables, on copies of the donated
            # operands (tok is donated by _sverify, cache by both)
            wargs = draft_args
            if self.donate:
                cache_c = jax.tree_util.tree_map(jnp.copy, draft_args[2])
                wargs = draft_args[:2] + (cache_c,) + draft_args[3:]
            with self._kernel_ctx(), _quiet_donation():
                dts, dps, cc = self._sdraft(*wargs, *sig)
                jax.block_until_ready(self._sverify(
                    self.params, jnp.copy(self.tokens), dts, dps, cc,
                    consts["temps"], consts["topks"], consts["active"],
                    self.key, consts["uids"], counts_j, gi_j, *sig,
                ))
            self._warmed.add(("spec",) + sig)
        t0 = time.perf_counter()
        with self._kernel_ctx(), _quiet_donation():
            drafts, dprobs, cache = self._sdraft(*draft_args, *sig)
            rows, n_acc, tok, self.cache = self._sverify(
                self.params, self.tokens, drafts, dprobs, cache,
                consts["temps"], consts["topks"], consts["active"],
                self.key, consts["uids"], counts_j, gi_j, *sig,
            )
            tok.block_until_ready()
        t1 = time.perf_counter()
        self.decode_wall_s += t1 - t0
        self.decode_steps += g + 1
        self.dispatches += 2  # draft scan + verify chunk
        self.spec_rounds += 1
        self.tokens = tok
        if self.pool is not None:
            self.pool.adopt_tables(self.cache.get("tables"))
        host_rows = self._fetch_block(rows)  # (B, G+1) — the round's sync
        n_np = np.asarray(n_acc)
        self.block_fetches += 1
        live = [i for i in range(self.max_batch) if active[i]]
        for i in live:
            n = int(n_np[i])
            gii = int(gi[i])
            self.draft_tokens += gii
            self.verify_tokens += gii + 1
            self.accepted_draft_tokens += n
            s = self.slots[i]
            rec = self._spec_req.setdefault(s.uid, [0, 0])
            rec[0] += gii
            rec[1] += n
            for t in range(n + 1):
                if self.slots[i] is None:
                    break  # stop rule fired mid-block: drop the tail
                self.slots[i].pos += 1  # mirror cache["len"] advancing
                self.spec_emitted_tokens += 1
                self._absorb(i, int(host_rows[i, t]), out, from_decode=True)
        if self.pool is not None:
            # host half of the rollback: lanes that stopped early (or
            # rejected drafts) release full-table pages past their
            # committed length; freed lanes were already released whole
            # by _absorb
            for i in live:
                if self.slots[i] is not None:
                    self.pool.rollback(i, self.slots[i].pos)
        t_end = time.perf_counter()
        self.sched_host_s += (t_sched - t_prefill_done) + (t_end - t1)
        return out

    # -- device-resident scheduler -------------------------------------------

    def _stage_fill(self) -> None:
        """Pre-stage queued prompts for on-device lane refill.

        Pops up to ``staged_lanes`` requests and pre-reserves each one's
        first-cycle pages (``PagedKVPool.stage_alloc`` — exposure capped
        by the write horizon, so a mid-loop swap can never write an
        unmapped page).  The ring is rebuilt every cycle: whatever the
        loop does not consume is released and pushed back to the queue
        front at the cycle boundary (``_unstage``).  Staged admissions
        bypass the prefix index — they prefill token-by-token on device
        into fresh pages."""
        assert not self._staged
        while len(self._staged) < self.staged_lanes and self.queue:
            req = self.queue[0]
            seq = list(req.prompt) + list(req.prefix)
            budget = req.sampling.max_new_tokens - len(req.prefix)
            rec = None
            if self.pool is not None:
                rec = self.pool.stage_alloc(len(seq), budget, self._horizon)
                if rec is None:
                    break  # pool pressure: stop staging this cycle
            self.queue.popleft()
            toks = np.zeros((self.max_len,), np.int32)
            toks[: len(seq)] = seq
            self._staged.append(
                {"req": req, "rec": rec, "tokens": toks, "len": len(seq)}
            )

    def _unstage(self, skip: int = 0) -> None:
        """Return staged-but-unconsumed entries (ring rows >= ``skip``) to
        the queue front, releasing their pre-reserved pages."""
        rest = self._staged[skip:]
        self._staged = []
        for e in reversed(rest):
            if e["rec"] is not None:
                self.pool.release_staged(e["rec"])
            self.queue.appendleft(e["req"])

    def _build_dstate(self) -> dict:
        """Device scheduler state, rebuilt wholesale from host bookkeeping
        at every cycle boundary (the host never reads it back — only the
        token block and the consumed-refill records round-trip)."""
        B, S = self.max_batch, self.max_len
        Q = max(1, self.staged_lanes)
        tok = np.zeros((B,), np.int32)
        live = np.zeros((B,), bool)
        occupied = np.zeros((B,), bool)
        pend = np.zeros((B,), np.int32)
        counts = np.zeros((B,), np.int32)
        budget = np.zeros((B,), np.int32)
        uids = np.zeros((B,), np.int32)
        temps = np.zeros((B,), np.float32)
        topks = np.zeros((B,), np.int32)
        eos = np.full((B,), -1, np.int32)
        feed_buf = np.zeros((B, S), np.int32)
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            occupied[i] = True
            uids[i] = s.uid
            temps[i] = s.sampling.temperature
            topks[i] = s.sampling.top_k
            eos[i] = s.sampling.eos_id
            counts[i] = len(s.generated)
            budget[i] = max(0, s.sampling.max_new_tokens - len(s.generated))
            if s.generated:
                tok[i] = s.generated[-1]
            if s.pending and s.feed:
                # mid-refill lane: the unfed prompt tail re-stages into
                # the lane's feed buffer and keeps draining on device
                feed_buf[i, : len(s.pending)] = s.pending
                pend[i] = len(s.pending)
                live[i] = True
            elif not s.pending:
                live[i] = True
            # host-chunked (non-feed) pending lanes stay occupied-not-live:
            # their length pins while the host keeps chunking next cycle
        s_tokens = np.zeros((Q, S), np.int32)
        s_len = np.zeros((Q,), np.int32)
        s_uid = np.zeros((Q,), np.int32)
        s_count0 = np.zeros((Q,), np.int32)
        s_temps = np.zeros((Q,), np.float32)
        s_topks = np.zeros((Q,), np.int32)
        s_eos = np.full((Q,), -1, np.int32)
        s_budget = np.zeros((Q,), np.int32)
        for r, e in enumerate(self._staged):
            req = e["req"]
            s_tokens[r] = e["tokens"]
            s_len[r] = e["len"]
            s_uid[r] = req.uid
            s_count0[r] = len(req.prefix)
            s_temps[r] = req.sampling.temperature
            s_topks[r] = req.sampling.top_k
            s_eos[r] = req.sampling.eos_id
            s_budget[r] = max(
                1, req.sampling.max_new_tokens - len(req.prefix)
            )
        # pack same-dtype vectors into stacked matrices: one host→device
        # transfer each instead of one per field (the jitted loop unpacks
        # rows at trace time).  row order is load-bearing — _dloop indexes
        # by position
        lanes_i = np.stack(
            [tok, live.astype(np.int32), occupied.astype(np.int32), pend,
             np.zeros((B,), np.int32),  # fed
             counts, budget, uids, topks, eos]
        )
        ring_i = np.stack([s_len, s_uid, s_count0, s_topks, s_eos, s_budget])
        d = {
            "lanes_i": jnp.asarray(lanes_i),
            "temps": jnp.asarray(temps),
            "feed_buf": jnp.asarray(feed_buf),
            "ring_i": jnp.asarray(ring_i),
            "s_temps": jnp.asarray(s_temps),
            "s_tokens": jnp.asarray(s_tokens),
            "scal": jnp.asarray([0, len(self._staged)], jnp.int32),
        }
        if self.pool is not None:
            lo = self.pool.layout
            if lo.has_full:
                s_tf = np.full((Q, lo.pages_full), lo.num_pages, np.int32)
                for r, e in enumerate(self._staged):
                    if e["rec"] is not None and e["rec"]["full_row"] is not None:
                        s_tf[r] = e["rec"]["full_row"]
                d["s_tbl_full"] = jnp.asarray(s_tf)
            if lo.win:
                s_tw = np.full((Q, lo.pages_win), lo.num_pages, np.int32)
                for r, e in enumerate(self._staged):
                    if e["rec"] is not None and e["rec"]["win_row"] is not None:
                        s_tw[r] = e["rec"]["win_row"]
                d["s_tbl_win"] = jnp.asarray(s_tw)
        return d

    def _replay(self, hb, steps: int, c_lane, c_step,
                out: list[GenerationResult]) -> int:
        """Mirror one dispatch's while-loop on the host: advance positions,
        absorb sampled tokens through the same stop rules the device
        applied (``_absorb``), and install refills at the iterations the
        device performed them.  Returns the number of staged ring rows
        this dispatch consumed."""
        by_step: dict[int, list[int]] = {}
        n = 0
        for r in range(c_lane.shape[0]):
            if c_step[r] >= 0:
                by_step.setdefault(int(c_step[r]), []).append(r)
                n += 1
        for t in range(steps):
            feeders: list[int] = []
            samplers: list[int] = []
            for i in range(self.max_batch):
                s = self.slots[i]
                if s is None:
                    continue
                if s.pending:
                    if s.feed:
                        feeders.append(i)
                    # host-chunked lanes froze on device: skip
                else:
                    samplers.append(i)
            for i in feeders + samplers:
                self.slots[i].pos += 1  # mirror cache["len"] advancing
            for i in feeders:
                s = self.slots[i]
                s.pending.pop(0)
                if not s.pending:
                    # the drain step also sampled the request's first token
                    self._absorb(i, int(hb[t, i]), out)
            for i in samplers:
                self._absorb(i, int(hb[t, i]), out, from_decode=True)
            for r in by_step.get(t, ()):
                # the device swapped staged ring row r into a dead lane at
                # the end of iteration t; its feeding starts at t+1
                lane = int(c_lane[r])
                e = self._staged[r]
                assert self.slots[lane] is None, (
                    "device refilled a lane the host still considers live"
                )
                if self.pool is not None and e["rec"] is not None:
                    self.pool.adopt_staged(lane, e["rec"])
                req = e["req"]
                self.slots[lane] = _Slot(
                    req, pos=0, seq=self._admit_seq,
                    pending=list(req.prompt) + list(req.prefix), feed=True,
                )
                self._admit_seq += 1
                self.admitted += 1
                self.refills += 1
                self._slots_dirty = True
        return n

    def _step_device(self) -> list[GenerationResult]:
        """One device-scheduler cycle: a full-drain host sync (admission,
        chunk drain, staging, runway reservation, state rebuild) followed
        by W chained run-until-stop dispatches (W=2 when async streaming),
        each fetched and replayed in launch order."""
        out: list[GenerationResult] = []
        self._admit(out)
        if self.prefill_chunk is not None or self._prefix is not None:
            # drain every host-chunked prompt before the (long) cycle: a
            # mid-chunk lane cannot join the device loop, and one chunk
            # per k_loop*W-step cycle would starve it
            while True:
                todo = sum(
                    len(s.pending) for s in self.slots
                    if s is not None and s.pending and not s.feed
                )
                if not todo:
                    break
                self._advance_chunks(out)
                left = sum(
                    len(s.pending) for s in self.slots
                    if s is not None and s.pending and not s.feed
                )
                if left >= todo:
                    break  # no progress (pool pressure): retry next cycle
        t_prefill_done = time.perf_counter()
        self._ensure_capacity(out)
        self._stage_fill()
        n_live = sum(
            1 for s in self.slots
            if s is not None and (not s.pending or s.feed)
        )
        self.max_concurrency = max(self.max_concurrency, n_live)
        if not n_live and not self._staged:
            return out
        self._util_sum += self._cache_utilization()
        self._util_n += 1
        self._kv_bytes_sum += self._live_kv_bytes()
        if self.pool is not None:
            if self.pool.pending_copies:
                self.cache = self.pool.apply_pending(self.cache)
            dt = self.pool.device_tables()
            if dt:  # ssm-only paged archs have no table'd layers
                self.cache["tables"] = dt
        dstate = self._build_dstate()
        need_sample = any(
            s is not None and s.sampling.temperature > 0 for s in self.slots
        ) or any(e["req"].sampling.temperature > 0 for e in self._staged)
        need_topk = any(
            s is not None and s.sampling.top_k > 0 for s in self.slots
        ) or any(e["req"].sampling.top_k > 0 for e in self._staged)
        sig = (self.k_loop, need_sample, need_topk)
        t_sched = time.perf_counter()
        if ("dloop",) + sig not in self._warmed:
            wargs = (self.params, self.cache, dstate, self.key)
            if self.donate:
                cache_c, dstate_c = jax.tree_util.tree_map(
                    jnp.copy, (self.cache, dstate)
                )
                wargs = (self.params, cache_c, dstate_c, self.key)
            with self._kernel_ctx(), _quiet_donation():
                jax.block_until_ready(self._dloop(*wargs, *sig))
            self._warmed.add(("dloop",) + sig)
        t0 = time.perf_counter()
        # launch all W dispatches up front: the scheduler state and cache
        # chain device-side, so dispatch w+1 is enqueued before dispatch
        # w's results exist — the double buffer async streaming rides on
        records = []
        cache = self.cache
        with self._kernel_ctx(), _quiet_donation():
            for _ in range(self._w):
                block, steps, c_lane, c_step, dstate, cache = self._dloop(
                    self.params, cache, dstate, self.key, *sig
                )
                records.append((block, steps, c_lane, c_step))
                self.dispatches += 1
        self.cache = cache
        if self.pool is not None:
            self.pool.adopt_tables(self.cache.get("tables"))
        t_launched = time.perf_counter()
        # fetch + replay in launch order: the block fetch of dispatch w
        # blocks on w alone, so host replay (and token streaming) of w
        # overlaps dispatch w+1 still executing on device
        consumed = 0
        fetch_s = 0.0
        host_s = 0.0
        for block, steps, c_lane, c_step in records:
            f0 = time.perf_counter()
            steps_i = int(steps)
            hb = self._fetch_block(block)
            c_lane_np = np.asarray(c_lane)
            c_step_np = np.asarray(c_step)
            f1 = time.perf_counter()
            self.block_fetches += 1
            self.decode_steps += steps_i
            consumed += self._replay(hb, steps_i, c_lane_np, c_step_np, out)
            host_s += time.perf_counter() - f1
            fetch_s += f1 - f0
        self.decode_wall_s += (t_launched - t0) + fetch_s
        # cycle boundary: retire the consumed ring prefix (adopted at
        # replay time), requeue the rest with their pages released
        self._unstage(skip=consumed)
        self.cycles += 1
        self.sched_host_s += (t_sched - t_prefill_done) + host_s
        return out

    def run(self) -> dict[int, GenerationResult]:
        """Drain the queue and all active slots; results keyed by uid."""
        results: dict[int, GenerationResult] = {}
        while self.queue or any(s is not None for s in self.slots):
            for r in self.step():
                results[r.uid] = r
        return results

    # -- reporting -----------------------------------------------------------

    def _cache_utilization(self) -> float:
        """Fraction of *reserved* cache token-slots holding live tokens.

        The slab reserves ``max_batch × max_len`` slots unconditionally;
        the paged pool reserves only its allocated pages — this ratio is
        what block-granular allocation buys on heterogeneous traffic.
        """
        lane_lens = {i: s.pos for i, s in enumerate(self.slots) if s is not None}
        if self.pool is not None:
            denom = self.pool.used_pages * self.pool.layout.page_size
            live = self.pool.live_tokens(lane_lens)
        else:
            denom = self.max_batch * self.max_len
            live = sum(min(p, self.max_len) for p in lane_lens.values())
        return live / denom if denom else 0.0

    def weight_bytes_per_step(self) -> int:
        """HBM weight bytes one decode step must read: every parameter leaf
        once, ``CompressedTensor`` leaves at their *stored* (compressed)
        size — the numerator of the N:M bandwidth win.  MoE archs overcount
        by the unrouted experts (all experts are resident; a step reads
        only top-k), so treat this as the dense-roofline bound.
        """
        total = 0
        for leaf in jax.tree_util.tree_leaves(
            self.params, is_leaf=lambda x: isinstance(x, CompressedTensor)
        ):
            total += int(leaf.nbytes)
        return total

    def _kv_row_bytes(self) -> tuple[int, int]:
        """(append-only, windowed) cache bytes per token per lane, summed
        over layers.  Constant for the engine's lifetime — computed once
        (step() calls this per decode step)."""
        if self._kv_row_b is not None:
            return self._kv_row_b
        cfg = self.model.cfg
        itemsize = jnp.dtype(cfg.param_dtype).itemsize
        plan = layer_plan(cfg)
        kinds = list(plan.head) + list(plan.period) * plan.n_body + list(plan.tail)
        full_b = win_b = 0
        windowed = (
            cfg.local_window is not None and cfg.local_window <= self.max_len
        )
        for kind in kinds:
            mixer = _block_mixer_mlp(kind, cfg)[0]
            if mixer == "attn":
                rb = 2 * cfg.n_kv * cfg.hd * itemsize
                if windowed:
                    win_b += rb
                else:
                    full_b += rb
            elif mixer == "mla":
                full_b += (cfg.mla.kv_lora + cfg.mla.rope_head_dim) * itemsize
        self._kv_row_b = (full_b, win_b)
        return self._kv_row_b

    def _live_kv_bytes(self) -> int:
        """KV bytes the *paged fast path* reads this step: each active
        lane's live tokens once.  (The gathered reference reads — and
        rewrites — the full ``B × S_max`` view instead; the slab engine
        has no choice.  This is the bytes-read-per-step roofline input.)"""
        full_b, win_b = self._kv_row_bytes()
        win = (
            min(self.max_len, self.model.cfg.local_window)
            if self.model.cfg.local_window is not None
            else self.max_len
        )
        total = 0
        for s in self.slots:
            if s is not None:
                total += full_b * min(s.pos + 1, self.max_len)
                total += win_b * min(s.pos + 1, win)
        return total

    def kv_cache_bytes(self) -> int:
        """Device bytes held by attention/MLA cache storage (slab or pool)."""
        plan = layer_plan(self.model.cfg)
        total = 0

        def entry_bytes(entry) -> int:
            return sum(x.nbytes for x in jax.tree_util.tree_leaves(entry))

        for i, kind in enumerate(plan.head):
            if _block_mixer_mlp(kind, self.model.cfg)[0] in ("attn", "mla"):
                total += entry_bytes(self.cache[f"head_{i}"])
        if plan.n_body:
            for j, kind in enumerate(plan.period):
                if _block_mixer_mlp(kind, self.model.cfg)[0] in ("attn", "mla"):
                    total += entry_bytes(self.cache["body"][f"sb_{j}"])
        for i, kind in enumerate(plan.tail):
            if _block_mixer_mlp(kind, self.model.cfg)[0] in ("attn", "mla"):
                total += entry_bytes(self.cache[f"tail_{i}"])
        return total

    def _mesh_ctx(self):
        """Dispatch mesh context (``kernels.dispatch.mesh_context``) under
        which ``shards > 1`` kernel calls may route to the shard_map
        wrappers; a no-op for a mesh-less engine."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.kernels import dispatch

        return dispatch.mesh_context(self.mesh)

    @contextlib.contextmanager
    def _kernel_ctx(self):
        """Context for executable calls: the mesh context, plus a record
        of every kernel route resolved inside.  ``jax.jit`` (re)traces
        lazily per signature, so the context must wrap *every* call, not
        just the first."""
        from repro.kernels import dispatch

        with dispatch.record_routes(self._routes), self._mesh_ctx():
            yield

    def kernel_routes(self) -> dict[str, str]:
        """kernel -> the route(s) its calls resolved to while this engine
        traced its executables, ``+``-joined when shapes split across
        routes (e.g. ``"pallas+xla"`` when a shape guard sent some matmul
        to XLA on a TPU).  Kernels never traced are absent."""
        modes: dict[str, list[str]] = {}
        for kernel, mode in sorted(self._routes):
            modes.setdefault(kernel, []).append(mode)
        return {k: "+".join(v) for k, v in modes.items()}

    def prefill_logits(self, tokens) -> jax.Array:
        """Logits ``(B, S, vocab)`` of the serving tree over ``tokens`` —
        the forward pass prefill runs, on this engine's placed weights and
        kernel routes.  Parity checks compare trees and meshes on it."""
        fwd = jax.jit(
            lambda p, t: self.model.forward(p, {"tokens": t}, remat=False)[0]
        )
        with self._kernel_ctx():
            return fwd(self.params, jnp.asarray(tokens, jnp.int32))

    def kernel_route(self) -> str:
        """The paged-attention route decode resolves at trace time —
        ``"shard_map"`` / ``"xla"`` / ``"pallas"`` / ``"interpret"`` for
        paged engines, ``"slab"`` when no paged kernel is in play.
        Mirrors the in-trace resolution (same mesh context + shape info)
        so benches can record which implementation a measured stream ran
        on without re-lowering the executable."""
        if self.pool is None:
            return "slab"
        from repro.kernels import dispatch

        lay = self.layout
        n_slots = lay.pages_full if lay.pages_full else lay.pages_win
        with self._mesh_ctx():
            mode, _ = dispatch.resolve(
                "paged_attn", b=self.max_batch, n_slots=n_slots,
                page_size=lay.page_size, num_pages=lay.num_pages,
                shards=lay.shards,
            )
        return mode

    def mesh_desc(self) -> Optional[dict]:
        """{"shape": [...], "axes": [...]} for the engine's mesh (None =
        single-device) — the ``mesh`` field of the serving summary."""
        if self.mesh is None:
            return None
        return {
            "shape": [int(s) for s in self.mesh.devices.shape],
            "axes": [str(a) for a in self.mesh.axis_names],
        }

    def sharding_report(self, include_hlo: bool = False) -> dict:
        """Per-shard placement facts for the mesh-native engine.

        Reports, per weight/cache leaf and in aggregate, the bytes one
        shard holds (``sharding.shard_shape``) next to the global bytes,
        plus which weight leaves ended up fully replicated (none should,
        for 2-D+ matmul weights on a model-axis mesh).  With
        ``include_hlo=True`` the decode executable is lowered + compiled
        for the engine's current shapes and its collective mix
        (all-reduce/all-gather/... counts and bytes) and per-argument input
        shardings are extracted — the "live executable" view the sharded
        serving tests assert on.
        """
        import math

        def shard_bytes(x) -> int:
            if self.mesh is not None and hasattr(x, "sharding"):
                return (
                    math.prod(x.sharding.shard_shape(x.shape))
                    * x.dtype.itemsize
                )
            return int(x.size * x.dtype.itemsize)

        from repro.utils.tree import _path_str

        weights = {}
        for path, leaf in jax.tree_util.tree_leaves_with_path(self.params):
            weights[_path_str(path)] = {
                "bytes": int(leaf.size * leaf.dtype.itemsize),
                "bytes_per_shard": shard_bytes(leaf),
                "ndim": int(leaf.ndim),
                "replicated": (
                    bool(leaf.sharding.is_fully_replicated)
                    if hasattr(leaf, "sharding") else True
                ),
            }

        def is_matmul_leaf(name: str, w: dict) -> bool:
            # per-feature vectors (norm scales, biases — stacked ones are
            # 2-D) replicate by design; counting them would bury a real
            # weight-replication regression in constant noise
            return w["ndim"] >= 2 and not any(
                f in name for f in ("bias", "norm", "scale")
            )
        cache_total = cache_shard = 0
        for leaf in jax.tree_util.tree_leaves(self.cache):
            cache_total += int(leaf.size * leaf.dtype.itemsize)
            cache_shard += shard_bytes(leaf)
        report = {
            "mesh": self.mesh_desc(),
            "weights": weights,
            "weight_bytes": sum(w["bytes"] for w in weights.values()),
            "weight_bytes_per_shard": sum(
                w["bytes_per_shard"] for w in weights.values()
            ),
            # the regression signal: matmul weights (ndim >= 2, not a
            # per-feature vector) that ended up fully replicated — 0 on a
            # healthy model-axis mesh
            "replicated_matmul_leaves": sum(
                1 for name, w in weights.items()
                if w["replicated"] and is_matmul_leaf(name, w)
            ),
            "cache_bytes": cache_total,
            "cache_bytes_per_shard": cache_shard,
        }
        if include_hlo:
            from repro.utils import hlo_cost as HC

            consts = self._slot_consts()
            budget = jnp.zeros((self.max_batch,), jnp.int32)
            counts = jnp.zeros((self.max_batch,), jnp.int32)
            with self._kernel_ctx():
                lowered = self._decode.lower(
                    self.params, self.tokens, self.cache, consts["temps"],
                    consts["topks"], consts["active"], consts["keep"],
                    self.key, consts["eos"], budget, consts["uids"], counts,
                    self.steps_per_dispatch, False, False,
                )
            compiled = lowered.compile()
            walk = HC.analyze(compiled.as_text())
            report["decode_collective_bytes"] = walk["collective_bytes"]
            report["decode_collective_total"] = walk["collective_total"]
            n_weight_leaves = len(jax.tree_util.tree_leaves(self.params))
            try:
                flat_in = jax.tree_util.tree_leaves(compiled.input_shardings[0])
                report["decode_weight_inputs_replicated"] = [
                    bool(s.is_fully_replicated)
                    for s in flat_in[:n_weight_leaves]
                ]
            except Exception:  # AOT introspection API drift: report omits it
                report["decode_weight_inputs_replicated"] = None
        return report

    def stats(self) -> dict:
        # throughput counts only decode-produced tokens over decode wall time;
        # each request's first token comes from (untimed) prefill and would
        # otherwise inflate tokens/s
        wb = self.weight_bytes_per_step()
        # _kv_bytes_sum is sampled once per host scheduling round: per
        # dispatch in sync mode, per cycle under the device scheduler
        kv_samples = self.cycles if self._device else self.dispatches
        kvb = self._kv_bytes_sum / kv_samples if kv_samples else 0.0
        total_wall = self.decode_wall_s + self.sched_host_s
        st = {
            "layout": self.layout.kind,
            "scheduler": "device" if self._device else "sync",
            "decode_steps": self.decode_steps,
            "dispatches": self.dispatches,
            "steps_per_dispatch": self.steps_per_dispatch,
            # a host sync is where scheduling can happen: every dispatch
            # in sync mode, one per round under speculation (draft+verify
            # chain device-side), only each full-drain cycle boundary
            # under the device scheduler
            "host_syncs": (
                self.cycles if self._device
                else (self.spec_rounds if self._spec else self.dispatches)
            ),
            "cycles": self.cycles,
            "block_fetches": self.block_fetches,
            "refills": self.refills,
            "max_steps_per_dispatch": self.k_loop,
            "staged_lanes": self.staged_lanes,
            "async_stream": self.async_stream,
            "itl_ms_p50": (
                float(np.percentile(self._itl_ms, 50)) if self._itl_ms else 0.0
            ),
            "itl_ms_p99": (
                float(np.percentile(self._itl_ms, 99)) if self._itl_ms else 0.0
            ),
            "donate": self.donate,
            "admitted": self.admitted,
            "preemptions": self.preemptions,
            "max_concurrency": self.max_concurrency,
            "prefill_batches": self.prefill_batches,
            "prefill_chunks": self.prefill_chunks,
            "tokens_generated": self.tokens_generated,
            "decode_tokens": self.decode_tokens,
            "decode_wall_s": self.decode_wall_s,
            "sched_host_s": self.sched_host_s,
            "kv_cache_bytes": self.kv_cache_bytes(),
            "hbm_cache_utilization": (
                self._util_sum / self._util_n if self._util_n else 0.0
            ),
            # per *logical token step*: device-side dispatch wall vs the
            # host-scheduling overhead amortized over the K tokens it buys
            "ms_per_decode_step": (
                self.decode_wall_s / self.decode_steps * 1e3
                if self.decode_steps
                else 0.0
            ),
            "ms_per_decode_step_host": (
                self.sched_host_s / self.decode_steps * 1e3
                if self.decode_steps
                else 0.0
            ),
            "host_overhead_frac": (
                self.sched_host_s / total_wall if total_wall > 0 else 0.0
            ),
            # decode-step roofline inputs: weight stream + mean live-KV read
            "weight_bytes_per_step": wb,
            "kv_bytes_per_step": kvb,
            "bytes_read_per_step": wb + kvb,
            "tokens_per_s": (
                self.decode_tokens / self.decode_wall_s
                if self.decode_wall_s > 0
                else 0.0
            ),
        }
        if self.pool is not None:
            lane_lens = {
                i: s.pos for i, s in enumerate(self.slots) if s is not None
            }
            used = self.pool.used_pages
            st["num_pages"] = self.pool.layout.num_pages
            st["page_size"] = self.pool.layout.page_size
            st["used_pages"] = used
            st["evicted_pages"] = self.pool.evicted_pages
            st["page_utilization"] = used / max(1, self.pool.layout.num_pages)
            live = self.pool.live_tokens(lane_lens)
            st["token_utilization"] = (
                live / (used * self.pool.layout.page_size) if used else 0.0
            )
            st["table_full_uploads"] = self.pool.table_full_uploads
            st["table_row_syncs"] = self.pool.table_row_syncs
            st["table_syncs"] = self.pool.table_syncs
            st["kv_quant"] = self.pool.layout.quant
            st["shared_pages"] = self.pool.shared_pages
            st["cow_copies"] = self.pool.cow_copies
        if self._prefix is not None:
            st["prefix_cache"] = True
            st["prefix_indexed_pages"] = self._prefix.pages
            st["prefix_evictions"] = self._prefix.evictions
            st["prefix_hits"] = self.prefix_hits
            st["prefix_hit_tokens"] = self.prefix_hit_tokens
            st["prefix_hit_rate"] = (
                self.prefix_hits / self.admitted if self.admitted else 0.0
            )
        if self._spec:
            w_d, w_v = self._spec_draft_bytes, self._spec_verify_bytes
            st["spec_gamma"] = self.spec_gamma
            st["spec_rounds"] = self.spec_rounds
            st["draft_tokens"] = self.draft_tokens
            st["verify_tokens"] = self.verify_tokens
            st["accepted_draft_tokens"] = self.accepted_draft_tokens
            st["spec_emitted_tokens"] = self.spec_emitted_tokens
            st["acceptance_rate"] = (
                self.accepted_draft_tokens / self.draft_tokens
                if self.draft_tokens else 0.0
            )
            st["accepted_per_verify"] = (
                self.spec_emitted_tokens / self.spec_rounds
                if self.spec_rounds else 0.0
            )
            st["draft_weight_bytes_per_step"] = w_d
            st["verify_weight_bytes_per_step"] = w_v
            # amortized weight stream per committed token: each round pays
            # gamma drafter sweeps + one verifier sweep
            st["bytes_per_accepted_token"] = (
                self.spec_rounds * (self.spec_gamma * w_d + w_v)
                / self.spec_emitted_tokens
                if self.spec_emitted_tokens else 0.0
            )
            st["spec_per_request"] = {
                uid: {
                    "drafted": d,
                    "accepted": a,
                    "acceptance_rate": a / d if d else 0.0,
                }
                for uid, (d, a) in sorted(self._spec_req.items())
            }
        return st
