"""Paged KV cache: a block allocator over one shared pool of token pages.

A cache that reserves ``max_out_tokens`` of KV per slot pins the same HBM
for a 30-token chat reply as for a 2k-token one, and bounds the slot count
by the worst-case request.  This module is the vLLM/PagedAttention answer
mapped onto the flash-decode stack, and the serving-time counterpart of the
ZeRO-Infinity argument (arXiv:2104.07857): treat KV memory as a managed
pool, not a static reservation.

Layout: the physical cache is ``[L, num_pages, Hkv, page_tokens, Dh]``
(one pool shared by every slot) and each slot owns an ordered list of
pages recorded in a ``[num_slots, slot_pages]`` int32 **page table**:
logical token ``t`` of a slot lives at row ``t % page_tokens`` of
physical page ``page_table[slot, t // page_tokens]``.  The table is host
state, shipped into every compiled program; reads indirect through it
(the Pallas flash-decode index map DMAs the right physical page per
block; the XLA fallback gathers a logical view) and per-row appends
scatter through it.

What a page holds depends on the model's attention form
(``serving/cache_kind.py`` decides, and owns the device arrays of the forms
below; this module counts pages).  Under
``attention="full"`` (every model but one) a page holds the K and V rows of
``page_tokens`` consecutive positions, for ever: a slot's pages grow by one
every ``page_tokens`` tokens, and prefix caching, the prefill->decode
hand-off and the host tier all rest on that (a page is position-pure: its
content is a function of the token prefix alone).  Under ``attention="eva"``
(``models/eva.py``; ``window_tokens`` / ``chunk_tokens`` below) a slot
holds TWO KINDS of page out of the same pool and the same free list, and
its table has two column ranges:

- **window pages**, columns ``[0, W / page)``: position ``p`` lives at row
  ``p % W``, so these ``W / page`` pages are filled once and then
  overwritten in place, window after window — a window page is NOT
  position-pure;
- **summary pages**, the columns after them: chunk summary ``c`` (one per
  ``chunk_tokens`` positions of a CLOSED window; ``ktilde`` in the K buffer,
  ``vtilde`` in the V buffer) lives at row ``c`` of the summary range, so a
  summary page stands for ``page * chunk_tokens`` tokens and a slot gains
  ``W / chunk_tokens`` summary rows each time a window closes.

Pages needed are therefore a function of the POSITION, not of the length
(:meth:`PagedKVPool.pages_for`): a slot never holds more than ``W / page``
window pages, and window pages are all granted before the first summary
page, so the table's columns still fill in order.  ``pool_tokens`` keeps
its meaning, rows of the pool: pages x ``page_tokens``.

**Two page budgets** (``ring_tokens`` below; a model whose layers are of
two kinds, ``models/afmoe.py``): the SLIDING layers keep only the last
``W = ring_tokens`` positions and the GLOBAL layers keep all of them, so
one page id cannot mean "a page in every layer".  The pool then holds two
budgets, each with its own device arrays (``cache_kind.TwoBudgets``:
``k_win`` / ``v_win`` ``[sliding layers, window pages, ...]``, ``k_full`` /
``v_full`` ``[global layers, full pages, ...]``), its own free list, its own
refcounts and its own junk page 0, and a slot's table has two column ranges:

- **window pages**, columns ``[0, W / page)``, ids into the window arrays:
  a RING of exactly ``W`` rows, position ``p`` at row ``p % W``, filled once
  and then overwritten in place.  Exactly ``W`` rows are enough in decode
  (position ``p`` overwrites ``p - W``, which query ``p`` no longer sees).
  A prefill chunk would overwrite rows its own first queries still attend,
  so THE CHUNK ATTENDS BEFORE IT APPENDS (``afmoe.cached_layers``: the ring
  as the earlier chunks left it beside the chunk's own K and V, each key
  masked by its position) and writes only its real rows: a pad row in a
  ring would destroy a live one.  So ``prefill_chunk <= W``;
- **full pages**, the columns after them, ids into the full arrays: the K
  and V of ``page_tokens`` consecutive positions, for ever.

``pages_for(tokens, kind)`` answers per kind (window: ``ceil(min(tokens, W)
/ page)``; full: ``ceil(tokens / page)``); :meth:`ensure` grants BOTH kinds'
pages or neither.  ``pool_tokens`` (the engine's ``kv_pool_tokens``) is the
FULL budget, positions the global layers can hold over all slots; the
window budget is not a knob of the engine: ``num_slots`` rings, so that a
slot that was admitted can always have its ring (``window_pool_tokens``
lowers it, for the allocator's own tests).  A 3 x W request holds ``W /
page`` window pages and ``3 W / page`` full ones, where one budget a layer
would hold ``3 W / page`` in every layer.

**Latent pages and a slot-state budget that is not pages** (a model of
latent-attention and linear-attention layers, ``models/kda_mla.py``;
``slot_state_bytes`` below).  A latent layer's page holds ONE row a position
that all heads share (``cache_kind.LatentPages``, alone where every layer is
a latent one, and ``LatentPagesAndState``: ``latent`` ``[latent layers,
pages, 1, page, row width]``, no V array: keys and values are read from the
same row); it is position-pure and allocated like a full-attention page, one
budget, one table column a page.  A linear layer keeps no rows at all but a
recurrent STATE of fixed size a slot (``state`` ``[linear layers, slots,
heads, d, d]`` float32 and ``tail`` ``[linear layers, slots, taps - 1,
channels]``, the short convolution's last inputs): it belongs to the slot,
not to a page, is never allocated or freed, and is RESET when a request
takes the slot (the engine's first chunk program of a request reads zeros
whatever the slot held, and counts ``ds_serve_state_resets_total``).
Under a learned selection of keys (``cache_kind.IndexedLatentPagesAndRing``)
a position of a latent layer is a latent row AND an index key (``index``
``[latent layers, pages, 1, page, index size]``, the same pages under the
same table), and a sliding latent layer keeps a RING a slot in the same
kind of budget as a state (``ring`` ``[sliding layers, slots, ring rows, the
kind's row width]``, ``ring rows`` the whole pages that hold a window: the
row of position p at ``p % ring rows``, attended where the position it
holds lies in the window; never reset, because a row is read only under the
position it was written for).  Beside PER-HEAD ``full_attention`` layers
(``cache_kind.FullPagesAndState``) the same state budget lies beside the K
and V arrays of the first paragraph, held for the full layers ALONE (``k`` /
``v`` ``[full layers, pages, Hkv, page, Dh]``, ``cfg.cache_layers`` of them:
a token costs those layers' rows and no others, 4 KB where one layer in four
is a full one at 8 key-value heads of 128).  ``ensure`` /
``release`` / ``check_no_leak`` keep their meaning, for pages.

Physical **page 0 is reserved as the junk page**: it is never allocated,
and a released slot's table rows all point at it, so the parked row's
junk K/V writes (inactive rows still execute in the static-shape compiled
step) land somewhere no live slot ever reads.

Allocation is host-side bookkeeping only (``ensure`` before a dispatch
covers the tokens it will write; ``release`` on finish) — the pool's
device arrays are owned and donated by the engine.  When the pool runs
dry the engine first evicts unreferenced prefix-cache pages (LRU), then
preempts the youngest-admitted slot (LIFO) and requeues it at the head of
the wait queue; the oldest request always keeps its pages, so admission
pressure cannot livelock the pool.

Prefix caching (``serving/prefix_cache.py``) rides on two extensions:

- **per-page refcounts** — a physical page may appear in several slots'
  page tables at once (``adopt`` INCREFs pages a new request shares
  read-only; ``release`` DECREFs, returning a page to the free list only
  when its last referencing slot lets go).  Shared pages are never
  written: prefill (re)starts at the match frontier, decode writes only
  at/after it, and a partially-matched boundary page is copied to a
  private page before the slot writes into it (copy-on-write — the
  engine's device-side page copy; the pool only swaps the bookkeeping).
- **cache pins** — pages held by the prefix cache (``pin``/``unpin``) are
  kept OFF the free list even at refcount 0, so a finished request's
  prompt KV survives for future admissions; eviction (``unpin``) is the
  cache's LRU decision, taken under pool pressure BEFORE any live slot is
  preempted.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.decoding import DECODE_BLOCK
from deepspeed_tpu.models.eva import closed_summary_rows, summary_rows


def default_page_tokens(max_out_tokens: int) -> int:
    """Page granularity when the config leaves it 0: the flash-decode
    block (pages ARE the kernel's DMA blocks), capped at the smallest
    power of two covering the per-slot budget so tiny configs don't round
    a 64-token budget up to one 256-token page."""
    from deepspeed_tpu.inference.engine import pow2_bucket

    return min(DECODE_BLOCK, pow2_bucket(max_out_tokens, lo=8))


def init_paged_kv_cache(cfg, num_pages: int, page_tokens: int,
                        dtype=jnp.bfloat16,
                        quantized: bool = False) -> Dict[str, Any]:
    """Device arrays for the shared page pool — the paged analog of
    :func:`~deepspeed_tpu.models.decoding.init_kv_cache`, with the slot
    dim replaced by the page dim and the sequence dim by the page depth:
    one budget of per-head K and V pages in every cache layer (a looped
    stack has one a (pass, layer) pair, a model whose other layers keep a
    state has the full layers only: ``cfg.cache_layers``, all under the one
    page table).  The arrays of the other kinds of cache are their
    kinds' (``serving/cache_kind.py``)."""
    L, Hkv, Dh = cfg.cache_layers, cfg.num_kv_heads, cfg.head_dim
    if quantized:
        return {
            "k": jnp.zeros((L, num_pages, Hkv, page_tokens, Dh), jnp.int8),
            "v": jnp.zeros((L, num_pages, Hkv, page_tokens, Dh), jnp.int8),
            "k_scale": jnp.zeros((L, num_pages, Hkv, page_tokens, 1),
                                 jnp.float32),
            "v_scale": jnp.zeros((L, num_pages, Hkv, page_tokens, 1),
                                 jnp.float32),
            "x_dtype": jnp.zeros((), dtype),
        }
    return {
        "k": jnp.zeros((L, num_pages, Hkv, page_tokens, Dh), dtype),
        "v": jnp.zeros((L, num_pages, Hkv, page_tokens, Dh), dtype),
    }


class PagedKVPool:
    """Host-side free-list allocator for the page pool.

    Parameters
    ----------
    num_slots:
        Slots (page-table rows) sharing the pool.
    max_out_tokens:
        Per-slot LOGICAL budget (prompt + generation), rounded up to a
        page multiple for the physical table depth (``cache_len``).
    page_tokens:
        Tokens per page (0 = :func:`default_page_tokens`).
    pool_tokens:
        Total pool capacity in tokens (0 = ``num_slots * cache_len``: a
        full budget for every slot, allocated on demand).  Setting it
        lower oversubscribes slots against a fixed HBM budget; the pool
        always holds at least one slot's full budget so a lone request can
        never deadlock.
    window_tokens, chunk_tokens:
        EVA attention's window and chunk (0 = full attention): the slot's
        table is then ``window_tokens / page`` window columns followed by
        the summary columns (module docstring), and ``cache_len`` stays the
        LOGICAL budget in positions, which the table no longer spans.
    ring_tokens, window_pool_tokens:
        Two page budgets (module docstring): the sliding layers' window
        (0 = one budget).  The slot's table is ``ring_tokens / page`` window
        columns, ids into the window budget, then ``cache_len / page`` full
        columns; ``pool_tokens`` sizes the full budget and
        ``window_pool_tokens`` the window budget (0 = ``num_slots`` rings).
    slot_state_bytes:
        Bytes of fixed per-slot state a slot carries beside its pages (module
        docstring; 0 = none): a budget of ``num_slots`` times it that is
        never allocated or freed, only reset by the engine's chunk program.
    """

    def __init__(self, num_slots: int, max_out_tokens: int, *,
                 page_tokens: int = 0, pool_tokens: int = 0,
                 window_tokens: int = 0, chunk_tokens: int = 0,
                 ring_tokens: int = 0, window_pool_tokens: int = 0,
                 slot_state_bytes: int = 0):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        self.state_bytes = num_slots * int(slot_state_bytes)
        self.page = int(page_tokens) or default_page_tokens(max_out_tokens)
        self.window, self.chunk = int(window_tokens), int(chunk_tokens)
        self.ring = int(ring_tokens)
        self.cache_len = -(-int(max_out_tokens) // self.page) * self.page
        if self.ring:
            if self.window or self.ring % self.page:
                raise ValueError(
                    f"a ring of {self.ring} tokens must be a whole number "
                    f"of {self.page}-token pages (and not EVA's window)")
            self.window_pages = self.ring // self.page
            self.summary_pages = 0
            self.slot_pages = self.window_pages + self.cache_len // self.page
        elif self.window:
            if self.window % self.page:
                raise ValueError(
                    f"EVA window of {self.window} tokens is not a whole "
                    f"number of {self.page}-token pages")
            self.window_pages = self.window // self.page
            self.summary_pages = -(-summary_rows(
                max_out_tokens, self.window, self.chunk) // self.page)
            self.slot_pages = self.window_pages + self.summary_pages
        else:
            self.window_pages, self.summary_pages = 0, 0
            self.slot_pages = self.cache_len // self.page
        full_pages = self.slot_pages - (self.window_pages if self.ring else 0)
        want = int(pool_tokens) or num_slots * full_pages * self.page
        usable = max(full_pages, -(-want // self.page))
        self.num_pages = usable + 1          # + the reserved junk page 0
        self.num_slots = num_slots
        # unallocated entries point at the junk page
        self.page_table = np.zeros((num_slots, self.slot_pages), np.int32)
        self._owned: List[List[int]] = [[] for _ in range(num_slots)]
        # per-page SLOT refcount (prefix-cache sharing: the same physical
        # page may sit in several slots' tables); the junk page is never
        # counted
        self._ref = np.zeros(self.num_pages, np.int32)
        # pages pinned by the prefix cache: kept off the free list even at
        # refcount 0 until the cache evicts them (unpin)
        self._cached: set = set()
        # LIFO free list: released pages are reused first (locality, and
        # deterministic reuse for the preempt-resume tests)
        self._free: List[int] = list(range(usable, 0, -1))
        # the window budget of two: the same three structures again
        self.num_window_pages = 0
        if self.ring:
            want_w = (int(window_pool_tokens)
                      or num_slots * self.window_pages * self.page)
            usable_w = max(self.window_pages, -(-want_w // self.page))
            self.num_window_pages = usable_w + 1
            self._owned_win: List[List[int]] = [[] for _ in range(num_slots)]
            self._ref_win = np.zeros(self.num_window_pages, np.int32)
            self._free_win: List[int] = list(range(usable_w, 0, -1))

    # -- allocation ----------------------------------------------------
    def pages_for(self, tokens: int, kind: Optional[str] = None) -> int:
        """Pages a slot holds once positions ``[0, tokens)`` are written.
        Full attention: one every ``page`` tokens.  EVA: the window's pages
        (at most ``W / page``, then reused in place) plus the pages of the
        summary rows of the windows that have closed.  Two budgets: ``kind``
        ``"window"`` (the ring: ``ceil(min(tokens, W) / page)``) or
        ``"full"`` (``ceil(tokens / page)``); None is their sum."""
        tokens = int(tokens)
        if self.ring:
            n = {"window": -(-min(tokens, self.ring) // self.page),
                 "full": -(-tokens // self.page)}
            return n[kind] if kind else n["window"] + n["full"]
        if not self.window:
            return -(-tokens // self.page)
        return (-(-min(tokens, self.window) // self.page)
                + -(-closed_summary_rows(tokens, self.window, self.chunk)
                    // self.page))

    def ensure(self, slot: int, tokens: int) -> bool:
        """Grow the slot's table to cover ``tokens`` logical tokens.
        Returns False when the pool is exhausted — pages already granted
        stay with the slot (the caller evicts cached pages / preempts a
        victim and retries)."""
        if tokens > self.cache_len:
            raise ValueError(f"slot needs {tokens} tokens > per-slot budget "
                             f"{self.cache_len}")
        if self.ring:
            return self._ensure_both(slot, tokens)
        owned = self._owned[slot]
        need = self.pages_for(tokens)
        while len(owned) < need:
            if not self._free:
                return False
            p = self._free.pop()
            self.page_table[slot, len(owned)] = p
            owned.append(p)
            self._ref[p] += 1
        return True

    def _ensure_both(self, slot: int, tokens: int) -> bool:
        """Two budgets: grant the window AND the full pages the slot lacks,
        or, where either budget is short, none of them."""
        win, full = self._owned_win[slot], self._owned[slot]
        more_w = self.pages_for(tokens, "window") - len(win)
        more_f = self.pages_for(tokens, "full") - len(full)
        if more_w > len(self._free_win) or more_f > len(self._free):
            return False
        for _ in range(more_w):
            p = self._free_win.pop()
            self.page_table[slot, len(win)] = p
            win.append(p)
            self._ref_win[p] += 1
        for _ in range(more_f):
            p = self._free.pop()
            self.page_table[slot, self.window_pages + len(full)] = p
            full.append(p)
            self._ref[p] += 1
        return True

    def append_shared(self, slot: int, page: int) -> None:
        """Append ONE already-populated page to the slot's table, shared
        READ-ONLY (INCREF'd) — the unit step :meth:`adopt` loops, exposed
        separately so the host-tier admission can interleave adopting
        device-resident pages with promoting host-resident ones."""
        assert page != 0, "cannot adopt the junk page"
        owned = self._owned[slot]
        assert len(owned) < self.slot_pages, f"slot {slot} table full"
        self.page_table[slot, len(owned)] = page
        owned.append(page)
        self._ref[page] += 1

    def adopt(self, slot: int, pages: List[int]) -> None:
        """Pre-populate a freshly-admitted slot's table with pages another
        request already computed (prefix-cache hit): each page is INCREF'd
        and shared READ-ONLY — the adopting request's prefill starts past
        them and its decode writes only into later, privately-allocated
        pages.  The slot must not own anything yet (admission-time only)."""
        owned = self._owned[slot]
        assert not owned, f"adopt into non-empty slot {slot}: {owned}"
        for p in pages:
            self.append_shared(slot, p)

    def alloc_page(self) -> Optional[int]:
        """Pop one free page WITHOUT binding it to a slot (refcount 0,
        unpinned) — the host-tier promotion target: the engine streams the
        demoted payload into it, then the cache pins it and the admitting
        slot adopts it, all within one admission (the page is never left
        dangling across a scheduler step).  None when the pool is dry —
        the caller evicts/demotes and retries."""
        if not self._free:
            return None
        return self._free.pop()

    def release(self, slot: int) -> int:
        """DECREF every page the slot references and park its table rows
        on the junk page; returns the number of pages actually returned to
        the free list (shared/cache-pinned pages survive their owners)."""
        owned = self._owned[slot]
        freed = 0
        for p in owned:
            self._ref[p] -= 1
            if self._ref[p] == 0 and p not in self._cached:
                self._free.append(p)
                freed += 1
        owned.clear()
        if self.ring:
            for p in self._owned_win[slot]:
                self._ref_win[p] -= 1
                self._free_win.append(p)
                freed += 1
            self._owned_win[slot].clear()
        self.page_table[slot, :] = 0
        return freed

    # -- prefix-cache pins ---------------------------------------------
    def pin(self, page: int) -> None:
        """Keep ``page`` alive for the prefix cache: once its last slot
        releases it, it parks as a cached page instead of going free."""
        assert page != 0, "cannot pin the junk page"
        self._cached.add(page)

    def unpin(self, page: int) -> None:
        """Cache eviction: drop the pin; a page no slot references goes
        straight to the free list (its KV content stays intact until the
        page is reallocated and overwritten)."""
        self._cached.discard(page)
        if self._ref[page] == 0:
            self._free.append(page)

    def ref(self, page: int) -> int:
        """Live-slot references on ``page`` (the prefix cache's eviction
        eligibility check: only refcount-0 pages may be evicted)."""
        return int(self._ref[page])

    # -- accounting ----------------------------------------------------
    @property
    def pages_used(self) -> int:
        """Distinct physical pages referenced by at least one slot (a
        shared page counts once — it occupies one page of HBM)."""
        used = int((self._ref > 0).sum())
        return used + int((self._ref_win > 0).sum()) if self.ring else used

    @property
    def pages_free(self) -> int:
        """Free pages (two budgets: of both; a slot may still be refused
        with pages free, in the other budget)."""
        return len(self._free) + (len(self._free_win) if self.ring else 0)

    @property
    def pages_cached(self) -> int:
        """Pages pinned by the prefix cache (shared pages a live slot
        also references are included — the pin is what outlives them)."""
        return len(self._cached)

    def slot_pages_used(self, slot: int) -> int:
        return len(self._owned[slot]) + (
            len(self._owned_win[slot]) if self.ring else 0)

    def pages_used_by_kind(self) -> Dict[str, int]:
        """Pages held by slots as ``{"window": n, "summary": n}`` (EVA; a
        slot's first ``W / page`` pages are its window's), or ``{"window":
        n, "full": n}`` (two budgets).  Under full attention every page
        counts as ``"window"``."""
        if self.ring:
            return {"window": sum(len(o) for o in self._owned_win),
                    "full": sum(len(o) for o in self._owned)}
        held = [len(o) for o in self._owned]
        cap = self.window_pages or self.slot_pages
        window = sum(min(n, cap) for n in held)
        return {"window": window, "summary": sum(held) - window}

    def owned(self, slot: int) -> List[int]:
        """The slot's page ids in logical order (a copy — the engine's
        prefix-cache insertion reads the prompt's page span from here)."""
        return list(self._owned[slot])

    def utilization(self, live_tokens: int) -> float:
        """live-tokens / allocated-page-tokens (1.0 = every allocated page
        row holds a live token).  With prefix sharing the ratio
        can exceed 1 — several slots' live tokens backed by one physical
        page is precisely the memory the cache saves."""
        alloc = self.pages_used * self.page
        return (live_tokens / alloc) if alloc else 0.0

    def check_no_leak(self) -> None:
        """Invariant probe (tests): every non-junk page is accounted for
        exactly once across {slot-referenced, cache-pinned, free} —
        refcounts equal the number of owning slots, pages no slot or cache
        holds are all on the free list, and nothing live is free.  Two
        budgets: the same of each."""
        self._check_budget(self._owned, self._ref, self._free, self._cached,
                           self.num_pages, "")
        if self.ring:
            self._check_budget(self._owned_win, self._ref_win,
                               self._free_win, set(), self.num_window_pages,
                               "window ")

    @staticmethod
    def _check_budget(owned, ref, free_list, cached, num_pages, what) -> None:
        counts: Dict[int, int] = {}
        for o in owned:
            assert len(o) == len(set(o)), f"slot owns a {what}page twice: {o}"
            for p in o:
                counts[p] = counts.get(p, 0) + 1
        assert 0 not in counts and 0 not in free_list \
            and 0 not in cached, f"{what}junk page allocated"
        for p in range(1, num_pages):
            assert ref[p] == counts.get(p, 0), (
                f"{what}page {p}: refcount {ref[p]} != "
                f"{counts.get(p, 0)} owning slot(s)")
        free = set(free_list)
        assert len(free) == len(free_list), f"{what}page on the free list twice"
        live = set(counts) | cached
        assert not (free & live), (
            f"live {what}pages on the free list: {free & live}")
        assert sorted(free | live) == list(range(1, num_pages)), (
            f"leaked {what}pages: referenced={sorted(counts)} "
            f"cached={sorted(cached)} free={sorted(free)}")
