"""Host-side paged KV-cache pool manager for the serving engine.

``PagedKVPool`` owns

- the device cache tree (one ``(num_pages, page_size, ...)`` pool array per
  attention/MLA layer, allocated via ``model.init_cache(layout=PagedLayout)``;
  SSM / RG-LRU states stay per-lane),
- the free-page list (page ids are *global*: one id reserves a
  ``page_size``-token block in **every** paged layer's pool at once), and
- the per-lane page tables, mirrored host-side in numpy and synced to the
  device (``cache["tables"]``) **incrementally**: mutations mark their lane
  dirty, and ``device_tables`` scatters only the dirty rows into the
  resident device arrays instead of re-uploading every lane's full table
  each step (the PR-3 engine re-built the whole ``tables`` dict per decode
  step).  The first call uploads everything once; steady-state decode with
  a K-step dispatch uploads ``O(dirty lanes)`` rows per *dispatch*, and
  zero when nothing changed.

Two tables exist, depending on what the architecture needs:

- ``full`` — append-only, ``ceil(max_len / page_size)`` slots per lane,
  used by non-windowed attention and MLA layers.  Slot ``p`` maps logical
  positions ``[p·ps, (p+1)·ps)``.
- ``win`` — modular, ``ceil((window + lookahead - 1) / page_size) + 1``
  slots per lane, used by sliding-window layers.  Position ``pos`` lives in
  slot ``(pos // ps) % n_slots``; when the window slides wholly past a page
  the page is evicted (returned to the free list) and its slot reused.  The
  ``lookahead`` widening guarantees the pages a K-step fused dispatch will
  write can all be pre-mapped *before* the dispatch without a modular slot
  collision against any page still live mid-scan.

The pool performs no scheduling itself: the engine asks ``can_admit`` /
``alloc_prefill`` at admission, ``ensure_steps(lane, pos, k)`` before every
decode dispatch (reserving *all* K writes so mid-scan exhaustion cannot
occur), and ``release`` on finish or preemption.  When the engine donates
the cache into its jitted executables it must hand the returned table
arrays back via ``adopt_tables`` — the device buffers the pool scattered
into were consumed by the donation.

**Shared pages (prefix caching).**  Pages are refcounted: ``_take`` hands
a page out at refcount 1, ``add_ref``/``decref`` adjust it, and a page
only returns to the free list when its count hits zero — ``release`` is a
decref over the lane's pages, so a prefix index (or another lane) holding
a reference keeps the KV resident after the original request finishes.
``alloc_prefill(..., shared_full=, shared_len=)`` maps an already-cached
prefix into a new lane's table instead of allocating fresh pages for it.
The invariant "never write into a page another holder can still read"
is enforced by **copy-on-write**: any write path about to touch a page
with refcount > 1 (the tail of a partially-shared page at admission, or
a decode write into a page the prefix index pinned) first repoints the
lane's table row at a fresh page and records a ``(src, dst)`` pair in
``pending_copies``; the engine materializes those as page-granular device
copies via ``apply_pending(cache)`` before its next dispatch.  Bookkeeping
(table rows, refcounts) commits immediately — only the bulk KV copy is
deferred to batch with the dispatch.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.cache import PagedLayout, cdiv, paged_layout_for


class PagedKVPool:
    """Free-page list + per-lane page tables over a shared device pool.

    **Mesh-native pools** (``mesh=...``): the physical pools are laid out
    across the mesh by ``distributed.compressed_pspecs.serving_cache_shardings``
    — each ``model``-axis shard owns a slice of the pages axis (the
    sequence-sharding analogue; ``kv_shard="feature"`` shards the trailing
    feature dim instead) while the page tables stay **replicated**, so every
    shard resolves logical→physical page addresses locally.  Table sync is
    still incremental, but each upload/row-scatter is a *per-shard*
    ``device_put``: the replicated ``NamedSharding`` fans the dirty rows out
    to every device, and the scatter onto the resident (committed) arrays
    keeps their sharding.  Allocation policy is unchanged — page ids are
    global, the host allocator doesn't know or care which shard physically
    backs a page.
    """

    def __init__(
        self,
        model,
        *,
        max_batch: int,
        max_len: int,
        num_pages: int,
        page_size: int = 16,
        dtype=None,
        lookahead: int = 1,
        mesh=None,
        kv_shard: str = "seq",
        quant: bool = False,
    ):
        shards = 1
        if mesh is not None:
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            shards = int(sizes.get("model", 1))
        self.layout: PagedLayout = paged_layout_for(
            model.cfg, max_len, page_size=page_size, num_pages=num_pages,
            lookahead=lookahead, shards=shards, quant=quant,
        )
        self.mesh = mesh
        self.kv_shard = kv_shard
        self.max_batch = max_batch
        self.max_len = max_len
        self.cache = model.init_cache(max_batch, max_len, dtype, layout=self.layout)
        self._table_shardings: Optional[dict] = None
        # the engine reuses this tree for its executables' in/out shardings
        self.cache_shardings: Optional[dict] = None
        if mesh is not None:
            from repro.distributed.compressed_pspecs import (
                check_kv_shard,
                serving_cache_shardings,
            )

            check_kv_shard(mesh, kv_shard)
            shd = serving_cache_shardings(
                mesh, self.cache, self.layout, kv_shard=kv_shard
            )
            self.cache = jax.device_put(self.cache, shd)
            self.cache_shardings = shd
            self._table_shardings = shd.get("tables")
        lo = self.layout
        self._pt_full = np.full((max_batch, lo.pages_full), lo.sentinel, np.int32)
        self._pt_win = np.full((max_batch, lo.pages_win), lo.sentinel, np.int32)
        self._free: list[int] = list(range(num_pages - 1, -1, -1))
        # per-lane bookkeeping: logical page no. -> page id
        self._full_pages: list[dict[int, int]] = [dict() for _ in range(max_batch)]
        self._win_pages: list[dict[int, int]] = [dict() for _ in range(max_batch)]
        self._dirty_lanes: set[int] = set(range(max_batch))
        self._dev_tables: Optional[dict] = None
        # page refcounts: 0 = free, 1 = privately owned, >1 = shared (a
        # lane plus the prefix index and/or other lanes).  used/free page
        # accounting is unchanged — a page is "used" while its count > 0.
        self._ref = np.zeros(num_pages, np.int32)
        # (src, dst) page pairs whose bulk KV copy is still pending; the
        # engine drains these via apply_pending(cache) before dispatching.
        # Each pending src holds one extra ref until the copy lands.
        self.pending_copies: list[tuple[int, int]] = []
        self.evicted_pages = 0  # whole pages freed by window sliding
        self.cow_copies = 0  # copy-on-write page forks
        # sync accounting (host-overhead reporting in the engine's stats)
        self.table_full_uploads = 0  # whole-table device uploads
        self.table_row_syncs = 0  # dirty rows scattered incrementally
        self.table_syncs = 0  # device_tables calls that moved any data

    # -- accounting ----------------------------------------------------------

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.layout.num_pages - len(self._free)

    @property
    def shared_pages(self) -> int:
        """Pages currently held by more than one reference."""
        return int((self._ref > 1).sum())

    def _win_span_pages(self, length: int) -> int:
        """Distinct pages covering the live window of a length-`length` seq."""
        if not self.layout.win or length <= 0:
            return 0
        ps = self.layout.page_size
        start = max(0, length - self.layout.win)
        return (length - 1) // ps - start // ps + 1

    def prefill_pages(self, prompt_len: int) -> int:
        """Pages a prompt needs *through its first decode write* at
        position ``prompt_len`` — reserving the next-write page up front
        keeps ``ensure_steps`` from preempting a freshly prefilled lane
        (which would waste the whole batched prefill)."""
        ps = self.layout.page_size
        boundary = 1 if prompt_len % ps == 0 else 0  # pos prompt_len opens a page
        full = (cdiv(prompt_len, ps) + boundary) if self.layout.has_full else 0
        win = self._win_span_pages(prompt_len)
        if self.layout.win:
            win += boundary
        return full + win

    def pages_for_request(self, cache_len_cap: int) -> int:
        """Worst-case concurrent pages over a request's whole lifetime."""
        ps = self.layout.page_size
        full = cdiv(cache_len_cap, ps) if self.layout.has_full else 0
        win = min(cdiv(cache_len_cap, ps), self.layout.pages_win)
        return full + (win if self.layout.win else 0)

    def live_tokens(self, lane_lens: dict[int, int]) -> int:
        """Cache tokens actually referenced, for utilization reporting."""
        tot = 0
        for length in lane_lens.values():
            if self.layout.has_full:
                tot += length
            if self.layout.win:
                tot += min(length, self.layout.win)
        return tot

    # -- allocation ----------------------------------------------------------

    def can_admit(self, prompt_len: int, shared_len: int = 0) -> bool:
        return self.fresh_prefill_pages(prompt_len, shared_len) <= len(self._free)

    def fresh_prefill_pages(self, prompt_len: int, shared_len: int = 0) -> int:
        """Fresh pages an admission needs when the first ``shared_len``
        prompt tokens are already backed by cached pages.  A mid-page
        shared boundary costs one extra page: the shared partial page is
        copy-on-write forked so the lane can write its tail."""
        if shared_len <= 0:
            return self.prefill_pages(prompt_len)
        ps = self.layout.page_size
        n_shared = cdiv(shared_len, ps)
        cow = 1 if shared_len % ps else 0
        return self.prefill_pages(prompt_len) - n_shared + cow

    def _take(self) -> int:
        pid = self._free.pop()
        self._ref[pid] = 1
        return pid

    def add_ref(self, pid: int) -> None:
        """Pin a live page (prefix index / shared-prefix admission)."""
        assert self._ref[pid] > 0, f"add_ref on free page {pid}"
        self._ref[pid] += 1

    def decref(self, pid: int) -> None:
        """Drop one reference; the page frees when the count hits zero."""
        assert self._ref[pid] > 0, f"decref on free page {pid}"
        self._ref[pid] -= 1
        if self._ref[pid] == 0:
            self._free.append(pid)

    def _cow_full(self, lane: int, pg: int) -> None:
        """Fork a shared full-table page the lane is about to write: map a
        fresh page in its place and queue the page-granular device copy.
        The source keeps one extra ref until ``apply_pending`` lands the
        copy (so it cannot be reallocated and overwritten first)."""
        src = self._full_pages[lane][pg]
        dst = self._take()
        self._ref[src] += 1  # pending-copy pin
        self.pending_copies.append((src, dst))
        self.cow_copies += 1
        self._full_pages[lane][pg] = dst
        self._pt_full[lane, pg] = dst
        self._dirty_lanes.add(lane)
        self.decref(src)  # the lane's own claim moves to dst

    def alloc_prefill(
        self,
        lane: int,
        prompt_len: int,
        shared_full: tuple[int, ...] = (),
        shared_len: int = 0,
        defer_win: bool = False,
    ) -> bool:
        """Map every page the prompt's cache entries land in, plus the page
        backing the first decode write at ``prompt_len``; False if short.

        ``shared_full`` maps already-cached pages (from the engine's prefix
        index) at logical full-table pages ``0..len(shared_full)-1`` —
        each gains a reference instead of coming off the free list, and
        only the uncached tail allocates fresh pages.  ``shared_len`` is
        the token length the shared pages cover; when it ends mid-page the
        last shared page is copy-on-write forked (the lane's tail prefill
        writes into it).  Shared prefixes require a full (append-only)
        table — windowed layouts evict pages, so the engine never offers
        them a shared prefix.

        No window eviction happens here: the prefill still scatters into
        the oldest window page, so it must stay mapped until the first
        ``ensure_steps`` (whose eviction runs after the prefill wrote).

        ``defer_win=True`` (windowed *chunked* prefill) maps no window
        pages at all: each chunk's pages are mapped just before its
        dispatch via ``ensure_steps(lane, start, csz)``, which also evicts
        pages the window slid past — the whole point of chunking a long
        windowed prompt is never holding its full page span at once."""
        assert not shared_full or (self.layout.has_full and not self.layout.win)
        assert shared_len < prompt_len or not shared_full
        if self.fresh_prefill_pages(prompt_len, shared_len) > len(self._free):
            return False
        lo, ps = self.layout, self.layout.page_size
        next_pg = prompt_len // ps  # page of the first decode write
        if lo.has_full:
            for pg, pid in enumerate(shared_full):
                self.add_ref(pid)
                self._full_pages[lane][pg] = pid
                self._pt_full[lane, pg] = pid
            if shared_full and shared_len % ps:
                self._cow_full(lane, len(shared_full) - 1)
            for pg in range(cdiv(prompt_len, ps)):
                if pg in self._full_pages[lane]:
                    continue
                pid = self._take()
                self._full_pages[lane][pg] = pid
                self._pt_full[lane, pg] = pid
            if next_pg not in self._full_pages[lane]:
                pid = self._take()
                self._full_pages[lane][next_pg] = pid
                self._pt_full[lane, next_pg] = pid
        if lo.win and prompt_len > 0 and not defer_win:
            start = max(0, prompt_len - lo.win)
            for pg in range(start // ps, (prompt_len - 1) // ps + 1):
                pid = self._take()
                self._win_pages[lane][pg] = pid
                self._pt_win[lane, pg % lo.pages_win] = pid
            if next_pg not in self._win_pages[lane]:
                pid = self._take()
                self._win_pages[lane][next_pg] = pid
                self._pt_win[lane, next_pg % lo.pages_win] = pid
        self._dirty_lanes.add(lane)
        return True

    def prompt_pages(
        self, lane: int, length: int
    ) -> tuple[list[int], Optional[int]]:
        """The full-table pages backing a lane's first ``length`` cached
        tokens, for prefix-index insertion: ``(complete_page_ids,
        partial_tail_id)`` where the tail id (None when ``length`` is
        page-aligned) holds only ``length % page_size`` valid tokens."""
        ps = self.layout.page_size
        n_full = length // ps
        full = [self._full_pages[lane][pg] for pg in range(n_full)]
        tail = self._full_pages[lane].get(n_full) if length % ps else None
        return full, tail

    def ensure_steps(self, lane: int, pos: int, k: int = 1) -> bool:
        """Back the next ``k`` decode writes at ``pos..pos+k-1``; False =
        pool full (nothing is allocated on failure — all-or-nothing, so a
        preemption retry sees the pool unchanged).

        Reserving the whole dispatch up front is what makes the K-step
        fused decode safe: the scan cannot run out of pages mid-flight, so
        the host only ever preempts at dispatch boundaries.  Also evicts
        whole window pages the sliding window has moved past *as of the
        first write* (eager, so another lane can claim them this very
        dispatch; pages expiring mid-scan are reclaimed at the next
        boundary).
        """
        lo, ps = self.layout, self.layout.page_size
        if lo.win:
            self._evict_win(lane, pos)
        k = max(1, min(k, self.max_len - pos))  # writes past max_len freeze
        pages = range(pos // ps, (pos + k - 1) // ps + 1)
        need_full = [
            pg for pg in pages if lo.has_full and pg not in self._full_pages[lane]
        ]
        # mapped pages the dispatch will write that another holder (the
        # prefix index, or a forked lane) can still read: copy-on-write
        # them, which costs one fresh page each
        cow_full = [
            pg
            for pg in pages
            if lo.has_full
            and pg in self._full_pages[lane]
            and self._ref[self._full_pages[lane][pg]] > 1
        ]
        need_win = [
            pg for pg in pages if lo.win and pg not in self._win_pages[lane]
        ]
        if len(need_full) + len(cow_full) + len(need_win) > len(self._free):
            return False
        for pg in cow_full:
            self._cow_full(lane, pg)
        for pg in need_full:
            pid = self._take()
            self._full_pages[lane][pg] = pid
            self._pt_full[lane, pg] = pid
            self._dirty_lanes.add(lane)
        for pg in need_win:
            pid = self._take()
            self._win_pages[lane][pg] = pid
            self._pt_win[lane, pg % lo.pages_win] = pid
            self._dirty_lanes.add(lane)
        return True

    def _evict_win(self, lane: int, pos: int) -> None:
        lo, ps = self.layout, self.layout.page_size
        start = max(0, pos - lo.win + 1)  # oldest live position after this write
        expired = [pg for pg in self._win_pages[lane] if (pg + 1) * ps - 1 < start]
        for pg in expired:
            pid = self._win_pages[lane].pop(pg)
            self.decref(pid)
            self.evicted_pages += 1
            if self._pt_win[lane, pg % lo.pages_win] == pid:
                self._pt_win[lane, pg % lo.pages_win] = lo.sentinel
            self._dirty_lanes.add(lane)

    def rollback(self, lane: int, new_len: int) -> None:
        """Truncate a lane's committed length to ``new_len`` after a
        speculative round: full-table pages past the one backing the next
        write (logical page ``new_len // page_size`` — kept, exactly the
        page ``alloc_prefill``/``ensure_steps`` keep mapped ahead of the
        write cursor) are *dereferenced*, not force-freed, so a shared
        prefix-cache page (or a COW fork another lane still reads) is
        never clobbered by rejected drafts — its other holders keep it
        resident and only this lane's claim drops.

        The speculative reservation this unwinds is a plain
        ``ensure_steps(lane, pos, gamma + 1)``: all-or-nothing, so a
        rejected tail can always be rolled back without the pool ever
        having been over-committed mid-round.  The device-side half of the
        truncation is the verify dispatch rewriting ``cache["len"]`` —
        stale KV past it is dead under the length masks every layout view
        applies, so no page contents need scrubbing.  Pages with a pending
        COW copy *into* them are skipped defensively (the engine drains
        ``pending_copies`` before any speculative dispatch, so none should
        exist here).  Windowed tables have no speculative seam (the engine
        gates ``spec_gamma`` off windowed archs) and are left untouched.
        """
        lo, ps = self.layout, self.layout.page_size
        if not lo.has_full:
            return
        keep = new_len // ps  # page of the next decode write stays mapped
        pend_dst = {d for _, d in self.pending_copies}
        for pg in [p for p in self._full_pages[lane] if p > keep]:
            pid = self._full_pages[lane][pg]
            if pid in pend_dst:
                continue
            del self._full_pages[lane][pg]
            self.decref(pid)
            if self._pt_full[lane, pg] == pid:
                self._pt_full[lane, pg] = lo.sentinel
            self._dirty_lanes.add(lane)

    def release(self, lane: int) -> None:
        """Drop the lane's reference on every page it holds (request
        finished or preempted).  Pages the prefix index (or a forked lane)
        still references stay resident; privately-held pages free."""
        for pg, pid in self._full_pages[lane].items():
            self.decref(pid)
        for pg, pid in self._win_pages[lane].items():
            self.decref(pid)
        if self._full_pages[lane] or self._win_pages[lane]:
            self._dirty_lanes.add(lane)
        self._full_pages[lane] = {}
        self._win_pages[lane] = {}
        self._pt_full[lane, :] = self.layout.sentinel
        self._pt_win[lane, :] = self.layout.sentinel

    # -- staged admissions (device-resident refill) --------------------------
    #
    # The device-resident scheduler swaps a queued request into a freed
    # lane *inside* the decode loop: the host pre-builds complete table
    # rows ("staged rows") with fresh pages backing every position the
    # device could write before the next host sync point, ships them as a
    # loop operand, and the in-loop refill copies a staged row over the
    # lane's row.  Staged pages are ordinary refcounted pages (off the
    # free list at count 1) that no lane's table references yet; on the
    # host-side replay of a consumed refill, ``adopt_staged`` installs the
    # row as the lane's mirror, and an unconsumed stage is returned via
    # ``release_staged``.

    def _stage_exposure(self, prompt_len: int, budget: int, horizon: int) -> int:
        """Positions ``0..e-1`` a staged request's refill may write before
        the host next reconciles: one scheduling cycle's worth of steps
        (``horizon``), capped by the request's own freeze point."""
        cap = min(self.max_len, prompt_len + max(1, budget))
        return min(max(1, horizon), cap)

    def staged_pages(self, prompt_len: int, budget: int, horizon: int) -> int:
        """Fresh pages one staged admission reserves."""
        lo = self.layout
        n = cdiv(self._stage_exposure(prompt_len, budget, horizon), lo.page_size)
        return n * ((1 if lo.has_full else 0) + (1 if lo.win else 0))

    def stage_alloc(
        self, prompt_len: int, budget: int, horizon: int
    ) -> Optional[dict]:
        """Reserve pages + build sentinel-padded table rows for a staged
        request; ``None`` when the pool is short (all-or-nothing).

        The returned record is host-only bookkeeping (numpy rows + page
        maps) — no lane's table row or device array is touched, so staging
        is safe while dispatches are in flight.
        """
        lo, ps = self.layout, self.layout.page_size
        if self.staged_pages(prompt_len, budget, horizon) > len(self._free):
            return None
        e = self._stage_exposure(prompt_len, budget, horizon)
        rec: dict = {
            "full_row": None, "win_row": None,
            "full_pages": {}, "win_pages": {}, "exposure": e,
        }
        if lo.has_full:
            row = np.full(lo.pages_full, lo.sentinel, np.int32)
            for pg in range(cdiv(e, ps)):
                pid = self._take()
                rec["full_pages"][pg] = pid
                row[pg] = pid
            rec["full_row"] = row
        if lo.win:
            row = np.full(lo.pages_win, lo.sentinel, np.int32)
            for pg in range(cdiv(e, ps)):
                pid = self._take()
                rec["win_pages"][pg] = pid
                row[pg % lo.pages_win] = pid
            rec["win_row"] = row
        return rec

    def release_staged(self, rec: dict) -> None:
        """Return an unconsumed stage's pages (request went back to the
        queue for a normal host admission)."""
        for pid in rec["full_pages"].values():
            self.decref(pid)
        for pid in rec["win_pages"].values():
            self.decref(pid)

    def adopt_staged(self, lane: int, rec: dict) -> None:
        """Install a consumed stage as ``lane``'s mappings (host replay of
        an in-loop refill).  The device's loop already holds exactly this
        row for the lane, and ``release`` of the lane's previous request
        already marked it dirty — the next sync rewrites identical values,
        which is harmless."""
        assert not self._full_pages[lane] and not self._win_pages[lane], (
            f"adopt_staged into occupied lane {lane}"
        )
        self._full_pages[lane] = dict(rec["full_pages"])
        self._win_pages[lane] = dict(rec["win_pages"])
        if rec["full_row"] is not None:
            self._pt_full[lane, :] = rec["full_row"]
        if rec["win_row"] is not None:
            self._pt_win[lane, :] = rec["win_row"]
        self._dirty_lanes.add(lane)

    # -- copy-on-write materialization ---------------------------------------

    _POOL_LEAVES = ("k", "v", "ckv", "krope")

    def apply_pending(self, cache: dict) -> dict:
        """Materialize queued copy-on-write forks as page-granular device
        copies on ``cache`` and return the updated tree.

        Must run on the *live* cache (the engine re-binds its tree from
        every donated executable, so the pool's own ``self.cache`` handle
        goes stale) before any dispatch that could write a forked page.
        Every paged pool leaf — KV arrays and their quantization scales —
        copies rows ``src → dst`` in one batched gather/scatter; chained
        pairs (a dst later re-forked as a src) fall back to per-pair order.
        Sources drop their pending pin afterwards, freeing any whose last
        reader was the fork itself."""
        if not self.pending_copies:
            return cache
        pairs = self.pending_copies
        self.pending_copies = []
        srcs = [s for s, _ in pairs]
        dsts = [d for _, d in pairs]
        chained = bool(set(srcs) & set(dsts))
        batches = [(s, d) for s, d in pairs] if chained else [(srcs, dsts)]

        def copy_rows(arr, stacked):
            for s, d in batches:
                si, di = jnp.asarray(s), jnp.asarray(d)
                arr = (
                    arr.at[:, di].set(arr[:, si])
                    if stacked
                    else arr.at[di].set(arr[si])
                )
            return arr

        def walk(node, shd, stacked):
            out = {}
            for name, v in node.items():
                if isinstance(v, dict):
                    sub = shd.get(name) if isinstance(shd, dict) else None
                    out[name] = walk(v, sub, stacked or name == "body")
                elif (
                    name in self._POOL_LEAVES or name.endswith("_scale")
                ) and hasattr(v, "ndim"):
                    nv = copy_rows(v, stacked)
                    if isinstance(shd, dict) and name in shd:
                        # eager scatters may drop the NamedSharding; re-pin
                        nv = jax.device_put(nv, shd[name])
                    out[name] = nv
                else:
                    out[name] = v
            return out

        cache = walk(cache, self.cache_shardings, False)
        for s in srcs:
            self.decref(s)
        return cache

    # -- device view ---------------------------------------------------------

    def device_tables(self) -> dict:
        """The page tables as device arrays, synced *incrementally*.

        The arrays are already in *kernel layout*: contiguous ``(max_batch,
        n_slots)`` int32 with the out-of-bounds sentinel ``num_pages`` in
        every unmapped slot — exactly the operand ``kernels.paged_attn``
        scalar-prefetches to compute page addresses, and the same array the
        gathered reference path indexes.  The first call uploads the whole
        tables once; after that only *dirty lanes* (rows touched since the
        last sync) are scattered into the resident device arrays — the
        steady-state decode dispatch moves ``O(changed rows)`` bytes, not
        ``O(max_batch × n_slots)``.
        """
        if self._dev_tables is None:
            t = {}
            put = (
                (lambda a, k: jax.device_put(a, self._table_shardings[k]))
                if self._table_shardings is not None
                else (lambda a, k: jnp.asarray(a))
            )
            if self.layout.pages_full:
                t["full"] = put(self._pt_full, "full")
            if self.layout.pages_win:
                t["win"] = put(self._pt_win, "win")
            self._dev_tables = t
            self._dirty_lanes.clear()
            self.table_full_uploads += 1
            self.table_syncs += 1
            # pre-compile every padded scatter shape (no-op scatters of row
            # 0 onto itself): the first dirty-row sync otherwise pays a
            # trace+compile inside a *timed* host-scheduling window, which
            # dominates short benches
            pad = 1
            while pad <= self.max_batch:
                self._scatter_rows(t, [0] * min(pad, self.max_batch))
                pad *= 2
            return self._dev_tables
        if self._dirty_lanes:
            rows = sorted(self._dirty_lanes)
            n_dirty = len(rows)
            # pad the row list to the next power of two (duplicate indices
            # rewrite identical rows) so every dirty count ≤ max_batch
            # reuses one of O(log max_batch) compiled scatter shapes
            pad = 1
            while pad < n_dirty:
                pad *= 2
            rows = rows + [rows[0]] * (pad - n_dirty)
            self._dev_tables = self._scatter_rows(dict(self._dev_tables), rows)
            self._dirty_lanes.clear()
            self.table_row_syncs += n_dirty
            self.table_syncs += 1
        return self._dev_tables

    def _scatter_rows(self, t: dict, rows: list) -> dict:
        idx = jnp.asarray(rows, jnp.int32)
        if self.layout.pages_full:
            t["full"] = t["full"].at[idx].set(jnp.asarray(self._pt_full[rows]))
        if self.layout.pages_win:
            t["win"] = t["win"].at[idx].set(jnp.asarray(self._pt_win[rows]))
        return t

    def adopt_tables(self, tables: Optional[dict]) -> None:
        """Re-anchor the incremental sync on the arrays a jitted call
        returned.  Required after any executable that *donates* the cache:
        the buffers ``device_tables`` last scattered into were consumed by
        the donation, and the returned (aliased) arrays are the live ones.
        Dirty lanes accumulated since remain dirty — they scatter onto the
        adopted arrays at the next sync."""
        if tables:
            self._dev_tables = dict(tables)
