"""Paged KV cache + prefix cache (paper §4.5, TPU adaptation) with an
optional host-DRAM spill tier (DESIGN.md §12).

TPU adaptation of PagedAttention (DESIGN.md §2): pages are 256 tokens (vs
vLLM's 16) so each page maps to one DMA-efficient VMEM tile; the paged
attention kernel consumes the block table as a scalar-prefetch operand.

Host-side allocator state (free list, block tables, refcounts, prefix hash
index) is plain Python — it runs on the serving coordinator.  Device arrays
hold the actual pages:

    k_pages, v_pages : (L, n_pages, Hkv, page_size, hd)

The page axis comes first (a page is one index on axis 1 across layers and
heads); inside a page the KV head precedes the token axis, so the kernel's
(page, head) tile is a full ``(page_size, hd)`` trailing block.

The prefix cache is content-addressed at page granularity: a full page of
committed tokens hashes (chained) to a page id; sessions sharing a prompt
prefix map their leading block-table entries to the same pages (copy-on-
write never needed — committed prefixes are immutable).

Tiering (DESIGN.md §12): with a `TierConfig`, cold pages spill from the
device pool to a host-memory pool instead of walling admission at
``OutOfPages``.  A page reference is either a device page id (``>= 0``)
or a spilled host handle encoded as ``~handle`` (``< 0``) — the two
states are disjoint by construction, so no page is ever simultaneously
resident and spilled.  Spill victims are chosen prefix-refcount-aware:
unreferenced prefix-cache pages go first (LRU by last-touch epoch), then
private (refcount == 1) pages of sequences idle past
``TierConfig.idle_epochs``; pages reachable from the prefix index with
refcount > 1 (a hot shared system prompt) are pinned and never spill.
Page-in restores the page bytes exactly — the int8 spill format only
quantizes a page when its dequantization round-trips bit-for-bit (raw
fallback otherwise), so a spill/reload cycle can never perturb the
committed stream.
"""
from __future__ import annotations

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

PAGE_SIZE = 256


class OutOfPages(RuntimeError):
    pass


class PageFault(RuntimeError):
    """A device-side consumer (block table, kernel staging) touched a
    spilled page reference — the engine must ``ensure_resident`` first."""


def is_spilled(ref: int) -> bool:
    """Page references are device ids (``>= 0``) or spilled host handles
    encoded as ``~handle`` (``< 0``)."""
    return ref < 0


@dataclasses.dataclass
class SeqPages:
    """Block table for one sequence: page refs covering positions
    [0, num_tokens).  Entries are device page ids or (tiered pools only)
    spilled ``~handle`` references."""

    pages: list          # [page_ref]
    num_tokens: int = 0  # valid tokens

    def capacity(self, page_size=PAGE_SIZE):
        return len(self.pages) * page_size


# ---------------------------------------------------------------------------
# Host spill tier
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class TierConfig:
    """Host-DRAM spill tier under the device page pool (DESIGN.md §12)."""

    #: host pool capacity in pages; 0 disables the tier
    host_pages: int = 0
    #: int8-quantize pages on spill (per-page, per-layer symmetric scales)
    #: — applied only when the dequantization round-trips bit-exactly,
    #: raw fallback otherwise, so reloads never perturb verification
    quantize: bool = False
    #: a sequence is a spill candidate once it has not been touched for
    #: this many allocator epochs (engine dispatches)
    idle_epochs: int = 2


@dataclasses.dataclass
class HostPage:
    """One spilled page: ``(2, L, Hkv, page_size, hd)`` K/V stacked."""

    fmt: str                      # "raw" | "int8"
    data: np.ndarray              # raw page bytes, or int8 codes
    scales: np.ndarray | None     # int8: (2, L) float32 per-(k/v, layer)
    dtype: np.dtype               # page dtype (reconstruction target)
    nbytes: int
    touched: int                  # allocator epoch at spill (host LRU key)
    owner: int | None             # owning seq_id; None = prefix-index only


class TieredPagePool:
    """Host-memory pool of spilled pages.

    ``put`` encodes (int8 when bit-exact and ``quantize`` is on, raw
    otherwise) and returns a monotonically-increasing handle; ``get``
    reconstructs the exact original page bytes.  Capacity is enforced by
    the caller (`PagedKV`) which evicts unreferenced (prefix-only)
    entries LRU before failing a spill.
    """

    def __init__(self, cfg: TierConfig, counters: dict | None = None):
        self.cfg = cfg
        self.entries: dict[int, HostPage] = {}
        self._next = 0
        self.counters = counters if counters is not None else {}
        for key in ("spill_bytes", "pagein_bytes", "pages_spilled",
                    "pages_paged_in", "spills_quantized", "spills_raw",
                    "host_evictions"):
            self.counters.setdefault(key, 0)

    @property
    def in_use(self) -> int:
        return len(self.entries)

    @property
    def free(self) -> int:
        return self.cfg.host_pages - len(self.entries)

    def _encode(self, page: np.ndarray):
        """int8 codes + per-(k/v, layer) scales when the dequantization is
        bit-exact; raw otherwise.  Lossy int8 would perturb target logits
        and flip accept decisions at the margin — incompatible with the
        byte-identity contract the golden battery enforces — so exactness
        is a structural property of the format, not a hope."""
        if self.cfg.quantize:
            amax = np.abs(page).reshape(page.shape[0], page.shape[1], -1) \
                .max(axis=-1)
            scales = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
            s = scales[:, :, None, None, None]
            codes = np.clip(np.rint(page.astype(np.float32) / s),
                            -127, 127).astype(np.int8)
            recon = (codes.astype(np.float32) * s).astype(page.dtype)
            if recon.tobytes() == page.tobytes():
                return "int8", codes, scales, codes.nbytes + scales.nbytes
        return "raw", page, None, page.nbytes

    def put(self, page: np.ndarray, *, epoch: int, owner: int | None) -> int:
        if self.free <= 0:
            raise OutOfPages(
                f"host spill pool full ({self.cfg.host_pages} pages)"
            )
        fmt, data, scales, nbytes = self._encode(page)
        self._next += 1
        self.entries[self._next] = HostPage(
            fmt=fmt, data=data, scales=scales, dtype=page.dtype,
            nbytes=nbytes, touched=epoch, owner=owner,
        )
        self.counters["pages_spilled"] += 1
        self.counters["spill_bytes"] += nbytes
        self.counters["spills_quantized" if fmt == "int8" else
                       "spills_raw"] += 1
        return self._next

    def get(self, handle: int) -> np.ndarray:
        """Exact reconstruction of the spilled page bytes."""
        e = self.entries[handle]
        self.counters["pages_paged_in"] += 1
        self.counters["pagein_bytes"] += e.nbytes
        if e.fmt == "raw":
            return e.data
        s = e.scales[:, :, None, None, None]
        return (e.data.astype(np.float32) * s).astype(e.dtype)

    def drop(self, handle: int) -> None:
        self.entries.pop(handle, None)


class PageAllocator:
    """Reference-counted page allocator with a content-addressed prefix
    index (chained page hashes) and LRU last-touch tracking.

    ``clock`` is the coarse allocation epoch (the engine ticks it once
    per dispatch); ``last_touch[pid]`` records the epoch a page was last
    allocated or used, which makes eviction (and tier-spill victim
    selection) explicitly LRU instead of dict-iteration order."""

    def __init__(self, n_pages: int, page_size: int = PAGE_SIZE):
        self.n_pages = n_pages
        self.page_size = page_size
        self.free: list[int] = list(range(n_pages - 1, -1, -1))
        self.refcount = np.zeros(n_pages, np.int32)
        # prefix cache: chain_hash -> page_ref ; page_ref -> chain_hash
        # (refs are device page ids, or ~handle for tier-spilled pages)
        self.prefix_index: dict[bytes, int] = {}
        self.page_hash: dict[int, bytes] = {}
        self.hits = 0
        self.misses = 0
        # LRU bookkeeping
        self.clock = 0
        self.last_touch = np.zeros(n_pages, np.int64)
        #: optional tier hooks installed by a tiered `PagedKV`:
        #: spill_hook(pid) -> ~handle | None (spill an unreferenced
        #: prefix page to the host tier instead of dropping its content);
        #: reclaim_hook(need) -> int (spill cold sequence pages, returns
        #: pages freed)
        self.spill_hook = None
        self.reclaim_hook = None

    # -- LRU clock ---------------------------------------------------------
    def tick(self) -> int:
        self.clock += 1
        return self.clock

    def touch(self, pid: int):
        self.last_touch[pid] = self.clock

    # -- raw alloc ---------------------------------------------------------
    def alloc(self) -> int:
        # reclaim lazily when exhausted: evict/spill unreferenced
        # prefix-cached pages first (LRU), then let the tier spill cold
        # sequence pages — shared (refcount > 1) pages are never touched
        if not self.free:
            self._evict_unreferenced(need=1)
        if not self.free and self.reclaim_hook is not None:
            self.reclaim_hook(1)
        if not self.free:
            raise OutOfPages(f"all {self.n_pages} pages referenced")
        pid = self.free.pop()
        self.refcount[pid] = 1
        self.touch(pid)
        return pid

    def retain(self, pid: int):
        self.refcount[pid] += 1

    def release(self, pid: int):
        self.refcount[pid] -= 1
        if self.refcount[pid] <= 0 and pid not in self.page_hash:
            self.refcount[pid] = 0
            self.free.append(pid)
        # hashed pages stay resident (refcount 0) until evicted

    def _evict_unreferenced(self, need: int | None = None):
        """Evict unreferenced prefix-cached pages in explicit LRU order
        (last-touch epoch, page id as the tie-break) — dict-iteration
        order would make tier-spill ordering depend on insertion history.
        ``need`` bounds the eviction to the pages actually required, so a
        hot prefix entry survives pressure longer than a cold one.  With
        a tier attached the page content is spilled (index entry
        retargeted to the host handle) instead of destroyed."""
        stale = sorted(
            (pid for pid, h in self.page_hash.items()
             if pid >= 0 and self.refcount[pid] <= 0),
            key=lambda pid: (self.last_touch[pid], pid),
        )
        if need is not None:
            stale = stale[:need]
        for pid in stale:
            h = self.page_hash.pop(pid)
            ref = self.spill_hook(pid) if self.spill_hook is not None else None
            if ref is not None:
                self.prefix_index[h] = ref
                self.page_hash[ref] = h
            else:
                self.prefix_index.pop(h, None)
            self.refcount[pid] = 0
            self.free.append(pid)

    @property
    def in_use(self) -> int:
        return self.n_pages - len(self.free)

    @property
    def available(self) -> int:
        """Pages obtainable by an ``alloc()`` right now: the free list plus
        prefix-cached pages no live sequence references (lazily evictable)."""
        evictable = sum(
            1 for pid in self.page_hash
            if pid >= 0 and self.refcount[pid] <= 0
        )
        return len(self.free) + evictable

    # -- prefix cache ------------------------------------------------------
    @staticmethod
    def chain_hash(prev: bytes, tokens) -> bytes:
        h = hashlib.blake2b(digest_size=16)
        h.update(prev)
        h.update(np.asarray(tokens, np.int32).tobytes())
        return h.digest()

    def lookup_prefix(self, tokens, *, load_hook=None) -> tuple[list[int], int]:
        """Longest page-aligned cached prefix of ``tokens``.
        Returns (page_ids, n_cached_tokens); retains the returned pages.

        ``load_hook(ref) -> pid | None`` (installed by a tiered `PagedKV`)
        pages a spilled index entry back in; a load failure (device pool
        exhausted) truncates the cached prefix there instead of raising.
        Pages are retained as they are found so a page-in for entry k+1
        cannot evict the (still refcount-0) entry k mid-lookup."""
        pages: list[int] = []
        h = b"root"
        n = 0
        for s in range(0, len(tokens) - self.page_size + 1, self.page_size):
            h = self.chain_hash(h, tokens[s : s + self.page_size])
            pid = self.prefix_index.get(h)
            if pid is None:
                break
            if is_spilled(pid):
                pid = load_hook(pid) if load_hook is not None else None
                if pid is None:
                    break
            self.retain(pid)
            self.touch(pid)
            pages.append(pid)
            n += self.page_size
        if pages:
            self.hits += 1
        else:
            self.misses += 1
        return pages, n

    def publish_prefix(self, tokens, page_ids):
        """Register fully-filled pages of a committed prefix in the index."""
        h = b"root"
        for i, pid in enumerate(page_ids):
            s = i * self.page_size
            if s + self.page_size > len(tokens):
                break
            h = self.chain_hash(h, tokens[s : s + self.page_size])
            if h not in self.prefix_index:
                self.prefix_index[h] = pid
                self.page_hash[pid] = h


class PagedKV:
    """Device-side paged KV arrays + per-sequence block tables, with an
    optional host-DRAM spill tier (``tier=TierConfig(...)``)."""

    def __init__(
        self,
        n_layers: int,
        n_pages: int,
        n_kv_heads: int,
        head_dim: int,
        *,
        page_size: int = PAGE_SIZE,
        dtype=jnp.bfloat16,
        tier: TierConfig | None = None,
        counters: dict | None = None,
        device=None,
    ):
        self.page_size = page_size
        self.allocator = PageAllocator(n_pages, page_size)
        # Page 0 is reserved as the scratch/sink page: padded batch rows and
        # masked positions scatter their (garbage) K/V here, so real pages
        # are never clobbered by padding.  Block tables also pad with 0, so
        # reads of pad entries land on scratch and are masked by lengths.
        self.scratch_page = self.allocator.alloc()
        assert self.scratch_page == 0, "scratch must be page 0 (pad id)"
        #: where the pools and every page written back into them live
        #: (None: JAX's default device)
        self.device = device
        shape = (n_layers, n_pages, n_kv_heads, page_size, head_dim)
        self.k_pages = jnp.zeros(shape, dtype, device=device)
        self.v_pages = jnp.zeros(shape, dtype, device=device)
        self.tables: dict[int, SeqPages] = {}
        # -- host spill tier (DESIGN.md §12) -------------------------------
        self.tier = None
        self.seq_last_used: dict[int, int] = {}
        if tier is not None and tier.host_pages > 0:
            self.tier = TieredPagePool(tier, counters)
            self.allocator.spill_hook = self._spill_index_page
            self.allocator.reclaim_hook = self._reclaim_cold

    @property
    def tiered(self) -> bool:
        return self.tier is not None

    # -- sequence lifecycle -------------------------------------------------
    def open_seq(self, seq_id: int, prompt_tokens, *, share: bool = True) -> int:
        """Allocate a block table; reuse prefix pages.  Returns number of
        tokens already covered by the prefix cache.

        ``share=False`` skips the prefix lookup entirely — used when KV is
        not a pure function of the token ids (cross-attention families:
        the same prompt under different images/audio has different KV).

        Always leaves at least one prompt token uncovered: prefill logits
        for the final prompt position must be recomputed, and recomputed
        suffix K/V may only be written to pages this sequence owns — so a
        fully-cached, page-aligned prompt gives back its last cached page.
        """
        self.seq_last_used[seq_id] = self.allocator.clock
        if not share:
            self.tables[seq_id] = SeqPages(pages=[], num_tokens=0)
            return 0
        pages, n_cached = self.allocator.lookup_prefix(
            prompt_tokens, load_hook=self._load_index_page,
        )
        if n_cached >= len(prompt_tokens) and pages:
            self.allocator.release(pages.pop())
            n_cached -= self.page_size
        self.tables[seq_id] = SeqPages(pages=pages, num_tokens=n_cached)
        return n_cached

    def ensure_capacity(self, seq_id: int, n_tokens: int):
        t = self.tables[seq_id]
        while t.capacity(self.page_size) < n_tokens:
            t.pages.append(self.allocator.alloc())

    def trim_seq(self, seq_id: int):
        """Release pages past the last valid token (speculative rollback:
        K/V written for rejected draft tokens can strand whole tail pages)."""
        t = self.tables[seq_id]
        keep = -(-t.num_tokens // self.page_size)          # ceil
        while len(t.pages) > keep:
            self._release_ref(t.pages.pop())

    def close_seq(self, seq_id: int, committed_tokens=None):
        t = self.tables.pop(seq_id)
        self.seq_last_used.pop(seq_id, None)
        if committed_tokens is not None:
            self.allocator.publish_prefix(committed_tokens, t.pages)
        for ref in t.pages:
            self._release_ref(ref)

    def _release_ref(self, ref: int):
        """Release one block-table entry: a device page drops a refcount;
        a spilled page keeps its host entry only if the prefix index still
        reaches it (orphaned to prefix-only ownership), else it is freed."""
        if not is_spilled(ref):
            self.allocator.release(ref)
            return
        if ref in self.allocator.page_hash:
            self.tier.entries[~ref].owner = None      # prefix-only now
        else:
            self.tier.drop(~ref)

    def set_len(self, seq_id: int, n: int):
        self.tables[seq_id].num_tokens = n

    def seq_len(self, seq_id: int) -> int:
        return self.tables[seq_id].num_tokens

    def seq_pages(self, seq_id: int) -> int:
        return len(self.tables[seq_id].pages)

    def publish_seq_prefix(self, seq_id: int, tokens):
        """Register the sequence's full pages covering ``tokens`` in the
        prefix index (done right after prompt prefill so *concurrent*
        sessions with the same prompt share pages, not just later ones)."""
        self.allocator.publish_prefix(tokens, self.tables[seq_id].pages)

    # -- spill tier (DESIGN.md §12) ------------------------------------------
    def tick(self) -> int:
        """Advance the allocator's LRU epoch (the engine calls this once
        per dispatch — verify batch or prefill pass)."""
        return self.allocator.tick()

    def touch_seq(self, seq_id: int):
        """Mark a sequence (and its resident pages) used this epoch —
        protects it from being chosen as a spill victim by a co-scheduled
        sequence's page-in."""
        self.seq_last_used[seq_id] = self.allocator.clock
        for ref in self.tables[seq_id].pages:
            if not is_spilled(ref):
                self.allocator.touch(ref)

    def _page_bytes(self, pid: int) -> np.ndarray:
        """(2, L, Hkv, page_size, hd) stacked K/V of one device page."""
        return np.asarray(jax.device_get(
            jnp.stack((self.k_pages[:, pid], self.v_pages[:, pid]))
        ))

    def _host_make_room(self) -> bool:
        """Free one host-pool slot by dropping the LRU prefix-only entry
        (entries owned by a live sequence hold unrecoverable state and are
        never dropped)."""
        if self.tier.free > 0:
            return True
        victims = sorted(
            (h for h, e in self.tier.entries.items() if e.owner is None),
            key=lambda h: (self.tier.entries[h].touched, h),
        )
        if not victims:
            return False
        h = victims[0]
        hsh = self.allocator.page_hash.pop(~h, None)
        if hsh is not None:
            self.allocator.prefix_index.pop(hsh, None)
        self.tier.drop(h)
        self.tier.counters["host_evictions"] += 1
        return True

    def _spill_index_page(self, pid: int) -> int | None:
        """Allocator eviction hook: move an unreferenced prefix-cache page
        to the host tier; returns the spilled ``~handle`` reference (or
        None when the host pool is full — the entry is then dropped, the
        untiered behavior)."""
        if not self._host_make_room():
            return None
        handle = self.tier.put(self._page_bytes(pid),
                               epoch=self.allocator.clock, owner=None)
        return ~handle

    def _reclaim_cold(self, need: int) -> int:
        """Allocator exhaustion hook: spill private (refcount == 1) pages
        of sequences idle past ``idle_epochs``, coldest sequence first.
        Shared prefix pages (refcount > 1) are pinned; sequences touched
        this epoch are protected."""
        idle_after = self.tier.cfg.idle_epochs
        clock = self.allocator.clock
        victims = sorted(
            (sid for sid, last in self.seq_last_used.items()
             if sid in self.tables and clock - last >= idle_after),
            key=lambda sid: (self.seq_last_used[sid], sid),
        )
        freed = 0
        for sid in victims:
            if freed >= need:
                break
            freed += self.spill_seq(sid, max_pages=need - freed)
        return freed

    def spill_seq(self, seq_id: int, *, max_pages: int | None = None) -> int:
        """Spill a sequence's private pages to the host tier; returns the
        number of device pages freed.  Pages shared through the prefix
        index (refcount > 1) stay resident — a hot shared system prompt
        never spills."""
        t = self.tables[seq_id]
        freed = 0
        for i, ref in enumerate(t.pages):
            if max_pages is not None and freed >= max_pages:
                break
            if is_spilled(ref) or self.allocator.refcount[ref] != 1:
                continue
            if not self._host_make_room():
                break
            handle = self.tier.put(self._page_bytes(ref),
                                   epoch=self.allocator.clock, owner=seq_id)
            h = self.allocator.page_hash.pop(ref, None)
            if h is not None:       # published page: retarget the index
                self.allocator.prefix_index[h] = ~handle
                self.allocator.page_hash[~handle] = h
            t.pages[i] = ~handle
            self.allocator.refcount[ref] = 0
            self.allocator.free.append(ref)
            freed += 1
        return freed

    def _restore_page(self, handle: int) -> int:
        """Allocate a device page and write the spilled bytes back into
        it, exactly.  Raises OutOfPages when the device pool cannot cover
        it even after reclaiming."""
        page = self.tier.get(handle)
        pid = self.allocator.alloc()
        self.k_pages = self.k_pages.at[:, pid].set(
            jnp.asarray(page[0], dtype=self.k_pages.dtype, device=self.device))
        self.v_pages = self.v_pages.at[:, pid].set(
            jnp.asarray(page[1], dtype=self.v_pages.dtype, device=self.device))
        return pid

    def _load_index_page(self, ref: int) -> int | None:
        """Prefix-lookup hook: page a spilled index entry back in.  The
        restored page re-enters the index as a resident refcount-0 page
        (the caller retains it).  Returns None when the device pool is
        exhausted — the lookup truncates the cached prefix there."""
        handle = ~ref
        try:
            pid = self._restore_page(handle)
        except OutOfPages:
            return None
        h = self.allocator.page_hash.pop(ref, None)
        if h is not None:
            self.allocator.prefix_index[h] = pid
            self.allocator.page_hash[pid] = h
        # swap any live table references (a closed-then-republished page
        # cannot have one, but a refcount-1 published page can)
        for t in self.tables.values():
            for i, r in enumerate(t.pages):
                if r == ref:
                    t.pages[i] = pid
                    self.allocator.retain(pid)
        self.allocator.refcount[pid] -= 1     # alloc's count; owner(s) added
        if self.allocator.refcount[pid] < 0:
            self.allocator.refcount[pid] = 0
        self.tier.drop(handle)
        return pid

    def ensure_resident(self, seq_id: int) -> int:
        """Page every spilled entry of ``seq_id`` back onto the device
        (the engine calls this for each scheduled row before staging the
        block table, so the fused hot path never sees a fault).  Returns
        the number of pages paged in; raises OutOfPages (sequence state
        consistent, resumable) when the device pool cannot cover it."""
        t = self.tables[seq_id]
        self.seq_last_used[seq_id] = self.allocator.clock
        loaded = 0
        for i, ref in enumerate(t.pages):
            if not is_spilled(ref):
                continue
            pid = self._restore_page(~ref)
            h = self.allocator.page_hash.pop(ref, None)
            if h is not None:
                self.allocator.prefix_index[h] = pid
                self.allocator.page_hash[pid] = h
            t.pages[i] = pid
            self.tier.drop(~ref)
            loaded += 1
        return loaded

    def spilled_pages(self, seq_id: int) -> int:
        return sum(1 for r in self.tables[seq_id].pages if is_spilled(r))

    def spilled_tokens(self, seq_id: int) -> int:
        """Token capacity of ``seq_id``'s spilled pages — what a verify
        of this sequence must page in (the scheduler prices this)."""
        return self.spilled_pages(seq_id) * self.page_size

    def spillable_tokens(self) -> int:
        """Token capacity the tier could still free from the device pool:
        unreferenced prefix pages plus private pages of idle sequences,
        capped by host-pool headroom.  Joins the scheduler's live memory
        budget — admission sees through the spill tier."""
        if not self.tiered:
            return 0
        clock = self.allocator.clock
        idle_after = self.tier.cfg.idle_epochs
        cold = 0
        for sid, t in self.tables.items():
            if clock - self.seq_last_used.get(sid, clock) < idle_after:
                continue
            cold += sum(
                1 for r in t.pages
                if not is_spilled(r) and self.allocator.refcount[r] == 1
            )
        # unreferenced prefix pages are already counted by `available`
        # (free_tokens); only cold sequence pages extend the budget here
        headroom = self.tier.free + sum(
            1 for e in self.tier.entries.values() if e.owner is None
        )
        return min(cold, max(headroom, 0)) * self.page_size

    # -- memory accounting ---------------------------------------------------
    @property
    def free_tokens(self) -> int:
        """Token capacity obtainable without evicting any live sequence."""
        return self.allocator.available * self.page_size

    def resident_tokens(self, seq_ids=None) -> int:
        """Token capacity held ON DEVICE by the given (default: all) open
        sequences' block tables.  Shared prefix pages count once per
        sharing sequence — that is the prefix cache's capacity gain.
        Spilled pages are excluded: reloading them consumes free pages,
        so counting them here would double-budget the pool."""
        tabs = (
            self.tables.values()
            if seq_ids is None
            else [self.tables[s] for s in seq_ids]
        )
        return sum(
            sum(1 for r in t.pages if not is_spilled(r)) for t in tabs
        ) * self.page_size

    def committed_tokens(self) -> int:
        """Valid (length-pointer-covered) tokens across open sequences."""
        return sum(t.num_tokens for t in self.tables.values())

    # -- device I/O ----------------------------------------------------------
    def block_table(self, seq_ids, max_pages: int) -> np.ndarray:
        """(B, max_pages) int32 page ids, padded with 0 (masked by lengths)."""
        bt = np.zeros((len(seq_ids), max_pages), np.int32)
        for i, sid in enumerate(seq_ids):
            pg = self.tables[sid].pages[:max_pages]
            if any(is_spilled(r) for r in pg):
                raise PageFault(
                    f"seq {sid} has spilled pages; ensure_resident first"
                )
            bt[i, : len(pg)] = pg
        return bt

    def lengths(self, seq_ids) -> np.ndarray:
        return np.array([self.tables[s].num_tokens for s in seq_ids], np.int32)

    def write_tokens(self, seq_id: int, start: int, k_new, v_new):
        """Write K/V for [start, start+T) of one sequence.

        k_new/v_new: (L, T, Hkv, hd), token-major; the page stores each
        run head-major, ``(L, Hkv, n, hd)``.  Functional-update of the page arrays
        (on TPU this is the fused scatter inside the verify kernel; the
        host path keeps semantics identical).
        """
        if self.tiered:
            self.ensure_resident(seq_id)
        t = self.tables[seq_id]
        T = k_new.shape[1]
        self.ensure_capacity(seq_id, start + T)
        ps = self.page_size
        o = 0
        while o < T:
            pos = start + o
            pid = t.pages[pos // ps]
            off = pos % ps
            n = min(ps - off, T - o)
            self.k_pages = self.k_pages.at[:, pid, :, off : off + n].set(
                k_new[:, o : o + n].swapaxes(1, 2).astype(self.k_pages.dtype)
            )
            self.v_pages = self.v_pages.at[:, pid, :, off : off + n].set(
                v_new[:, o : o + n].swapaxes(1, 2).astype(self.v_pages.dtype)
            )
            o += n

    def gather_dense(self, seq_id: int, max_len: int):
        """Materialize (L, max_len, Hkv, hd) dense K/V for one sequence —
        reference/debug path."""
        if self.tiered:
            self.ensure_resident(seq_id)
        t = self.tables[seq_id]
        ps = self.page_size
        n_pages_needed = (max_len + ps - 1) // ps
        pads = t.pages[:n_pages_needed] + [0] * (n_pages_needed - len(t.pages))
        idx = np.asarray(pads, np.int32)

        def dense(pages):
            g = pages[:, idx].transpose(0, 1, 3, 2, 4)   # (L, n, P, Hkv, hd)
            return g.reshape(g.shape[0], -1, *g.shape[3:])[:, :max_len]

        return dense(self.k_pages), dense(self.v_pages)
