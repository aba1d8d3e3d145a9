"""What a slot's cache is made of, behind one interface.

``serving/paged_kv.py`` allocates pages and ``serving/engine.py`` schedules
requests; neither knows what a page holds.  That depends on the model's
attention form, and :func:`cache_kind` is the one place that decides it
(the layouts themselves are set out in ``serving/paged_kv.py``'s docstring):

- :class:`FullPages` (GPT-2, Mistral, OLMoE, Ouro): per-head K and V rows
  of ``page`` consecutive positions in every cache layer (``cfg.cache_layers``:
  a looped stack has one a (pass, layer) pair, all under the one table), for
  ever.  Position-pure, so prefix caching, the prefill -> decode hand-off,
  the host tier and the int8 cache all work on it: it refuses nothing;
- :class:`WindowSummaryPages` (``attention="eva"``, ``models/eva.py``);
- :class:`TwoBudgets` (sliding and global layers, ``models/afmoe.py``);
- :class:`LatentPages` (latent-attention layers only,
  ``models/kda_mla.py``): one row a position that all heads share;
- :class:`LatentPagesAndState` (linear-attention layers beside them): the
  same pages and a recurrent state a slot;
- :class:`FullPagesAndState` (linear-attention layers beside per-head
  ``full_attention`` layers; or the one-mixer form of ``models/ssm_moe.py``,
  mamba2 or mamba1 layers beside ``full_attention``, ``experts`` and ``mlp``
  layers):
  :class:`FullPages`' K and V arrays in the full
  layers ONLY (``cfg.cache_layers`` counts them, so a token costs those
  layers' bytes) and a state a slot, its shape the model module's
  (``afmoe.form(cfg).state_shapes``), under :class:`SlotState`'s budget;
- :class:`IndexedLatentPagesAndRing` (latent layers that attend a learned
  selection of their keys, beside sliding latent layers of other sizes): a
  latent row AND an index key a position under one page table, and a ring of
  the sliding layers' rows a slot.

A kind answers what the engine asks and nothing else: the pool's arguments
and the device arrays; what it cannot be served with (``cannot``: one table
of option and reason); the chunk program's ``view`` of one slot and its
``write_back``; the smallest bucket a chunk program is worth building for
(``chunk_rows``); its counters, moved on the host from positions the engine
holds anyway.  Every engine registers every kind's series (``attach``), so
what a replica exports does not depend on the model it serves.  A new layout
is one class here beside its model module.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import afmoe, kda_mla
from deepspeed_tpu.ops.pallas.decode import (eva_keys_fetched,
                                             paged_keys_fetched)
from deepspeed_tpu.ops.pallas.flash_attention import (_LANES,
                                                      eva_chunk_schedule,
                                                      mla_chunk_schedule)
from deepspeed_tpu.serving.paged_kv import init_paged_kv_cache


def _slot_view(v, pt_row, cols):
    """The pages of pool entry ``v`` ``[L, pages, Hkv, page, D]`` that a
    slot's page-table row names at columns ``cols``, as the contiguous
    ``[L, 1, Hkv, len(cols) * page, D]`` the prefill forward takes: one
    slice a page (a gather through the table, ``v[:, pt_row]``, compiles on
    the v5e to a read and a write-back of the whole donated pool)."""
    g = jnp.concatenate(
        [jax.lax.dynamic_slice_in_dim(v, pt_row[c], 1, axis=1)
         for c in cols], axis=1)                   # [L, n, Hkv, page, D]
    L, n, Hkv, page, D = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(L, 1, Hkv, n * page, D)


def _slot_write_back(dst, s, pt_row, col0, pages):
    """:func:`_slot_view`'s inverse, in place in the donated pool: page
    ``i`` of the view ``s`` (a Python or a traced index, for each ``i`` of
    ``pages``) goes to the pool page at column ``col0 + i`` of the row.
    Columns that name one pool page (unallocated entries all name junk page
    0) are written in turn and the last wins."""
    L, _, Hkv, S, D = s.shape
    page = dst.shape[3]
    paged = s.reshape(L, Hkv, S // page, page, D).transpose(0, 2, 1, 3, 4)
    for i in pages:
        one = jax.lax.dynamic_slice_in_dim(paged, i, 1, axis=1)
        dst = jax.lax.dynamic_update_slice_in_dim(
            dst, one, pt_row[col0 + i], axis=1)
    return dst


def _touched(start, cb: int, page: int, fp: int) -> List[Any]:
    """The pages of a slot's ``fp`` position-pure ones that a chunk of
    ``cb`` rows at ``start`` can have written, as indices into its view."""
    first = jnp.minimum(start // page, fp - 1)
    return [jnp.minimum(first + i, fp - 1)
            for i in range(min(-(-cb // page) + 1, fp))]


class FullPages:
    """Pages of per-head K and V rows of consecutive positions, the same in
    every layer (module docstring); the base of the other kinds."""

    what = "attention='full'"       # how a refusal names the model
    # option -> why this kind cannot be served with it: ``handoff`` (a role
    # other than "both", a ``prefill_only`` request), ``kv_host_tier_pages``,
    # ``quantize_kv_cache``, ``use_fused_decode`` (False), all refused by
    # name; ``prefix_caching``, which is turned off with the reason logged
    cannot: Dict[str, str] = {}
    # the series this kind moves
    counters: Dict[str, str] = {
        "ds_serve_attn_keys_attended_total":
            "(live row, decode step) keys the decode attention kernel over "
            "per-head K/V pages under one page table attends in one KV head "
            "of one cache layer: pos + 1 a row a step (EVA: the pos % W + 1 "
            "rows of its window and the W/C summaries of each closed one)",
        "ds_serve_attn_keys_fetched_total":
            "(live row, decode step) keys that kernel brings into VMEM for "
            "them: a row's pages before its last whole, the last in pieces "
            "of ops/pallas/decode.py:FETCH_ROWS tokens up to pos "
            "(paged_keys_fetched; whole at head dims under the lane tile; "
            "EVA: its window pages, then its summary pages, the last page of "
            "either in pieces: eva_keys_fetched)",
    }
    takes_valid_len = False         # the chunk's forward is told its real rows
    pages_by_kind = False           # ds_serve_kv_pages_used_by_kind moves
    # the query rows the chunk's attention pads a bucket to anyway: the
    # floor of the engine's chunk buckets (``ServingEngine.chunk_bucket``),
    # since a program under it saves no work and costs a compile.  Here the
    # attention pads nothing and a chat prompt does end in a short chunk
    chunk_rows = 8
    # the pool's arrays of pages ([layers, pages, heads, page, width]; a
    # kind holds those of them it has), and the ones whose leading extents
    # add up to its cache layers
    page_arrays = ("k", "v", "k_scale", "v_scale")
    layer_arrays = ("k",)

    def __init__(self, cfg):
        self.cfg = cfg

    # -- the pool and its arrays ---------------------------------------
    def pool_args(self, dtype) -> Dict[str, int]:
        """What ``PagedKVPool`` is told beside slots, budget and page."""
        return {}

    def init_cache(self, pool, num_slots: int, dtype, quantized: bool):
        return init_paged_kv_cache(self.cfg, pool.num_pages, pool.page,
                                   dtype=dtype, quantized=quantized)

    def layout(self, pool, num_slots: int) -> str:
        """The start-up log's account of the pool."""
        return (f"paged pool: {pool.num_pages - 1} x {pool.page}-token "
                f"pages, {num_slots} slots x {pool.cache_len} window")

    # -- what it cannot be served with ---------------------------------
    def refuse(self, option: str, asked: str) -> None:
        """Raise where the table lists ``option``; ``asked`` is how the
        caller spelled it."""
        if option in self.cannot:
            raise NotImplementedError(
                f"{asked} with {self.what}: {self.cannot[option]}")

    def check(self, config, role: str, prefill_chunk: int) -> None:
        """An engine's construction against the table, then the kind's own
        bound on ``prefill_chunk``."""
        if role != "both":
            self.refuse("handoff", f"role={role!r}")
        if int(getattr(config, "kv_host_tier_pages", 0)) > 0:
            self.refuse("kv_host_tier_pages", "kv_host_tier_pages > 0")
        if config.quantize_kv_cache:
            self.refuse("quantize_kv_cache", "quantize_kv_cache")
        if config.use_fused_decode is False:
            self.refuse("use_fused_decode", "use_fused_decode=False")
        self.check_prefill_chunk(int(prefill_chunk))

    def check_prefill_chunk(self, prefill_chunk: int) -> None:
        pass

    # -- the chunk program's view of one slot --------------------------
    def view(self, cache, pt_row, slot, start, cb: int):
        """The slot's rows as the contiguous cache ``forward_with_cache``
        takes for a chunk of ``cb`` rows at ``start``: its pages sliced out
        of the pool one by one (:func:`_slot_view`); what is no array of
        pages passes."""
        cols = range(pt_row.shape[0])
        return {k: (_slot_view(v, pt_row, cols) if k in self.page_arrays
                    else v) for k, v in cache.items()}

    def write_back(self, cache, sub, pt_row, slot, start, cb: int):
        """``sub``, the view as the forward left it, back in place in the
        donated pool (:func:`_slot_write_back`).  A chunk reads and writes
        ``slot_pages`` pages whatever the pool's size.  Pad rows hold junk
        but are only ever attended after the next chunk or decode step has
        overwritten them; junk past the allocated pages lands on the junk
        page."""
        cols = range(pt_row.shape[0])
        return {k: (_slot_write_back(cache[k], sub[k], pt_row, 0, cols)
                    if k in self.page_arrays else sub[k]) for k in cache}

    # -- counters ------------------------------------------------------
    def attach(self, registry, pool) -> None:
        """Register every kind's series and keep the registry: a kind moves
        its own, and does the arithmetic for them only while
        ``registry.enabled``."""
        for kind in KINDS:
            for name, what in kind.counters.items():
                registry.counter(name, what)
        registry.gauge(
            "ds_serve_state_bytes",
            "bytes of per-slot recurrent state and convolution tails "
            "resident on the device: num_slots times a slot's, fixed")
        self._pages_kind = {
            kind: registry.gauge(
                "ds_serve_kv_pages_used_by_kind",
                "KV pool pages held by slots, by what they hold (EVA: window "
                "rows reused in place, or chunk summaries; two budgets: the "
                "sliding layers' rings, or the global layers' full pages; "
                "full attention: all window; a learned selection: the "
                "sliding layers' rings, the latent pages, and the same "
                "pages' index keys)", labels={"kind": kind})
            for kind in ("window", "summary", "full", "index")}
        self._reg = registry
        self._page = pool.page
        self._m = {name: registry.counter(name) for name in self.counters}

    def cache_gauges(self, cache) -> None:
        """``ds_serve_kv_cache_layers`` and ``ds_serve_kv_bytes_per_token``,
        from the pool's arrays as built: fixed for the engine's life."""
        self._reg.gauge(
            "ds_serve_kv_cache_layers",
            "layers of the page pool's arrays: the model's layers that keep "
            "rows in pages, times the passes of a looped stack (one cache "
            "layer a (pass, layer) pair)").set(
                sum(cache[k].shape[0] for k in self.layer_arrays))
        self._reg.gauge(
            "ds_serve_kv_bytes_per_token",
            "bytes one position holds in the page pool: a row in every "
            "array of pages, over all their layers (int8 rows with their "
            "scales)").set(
                sum(cache[k].nbytes // (cache[k].shape[1] * cache[k].shape[3])
                    for k in self.page_arrays if k in cache))

    def count_admit(self) -> None:
        """A request took a slot."""

    def count_chunk(self, pool, cache, off: int, c: int, cb: int) -> None:
        """A chunk of ``c`` real tokens at ``off``, in a bucket of ``cb``,
        was enqueued."""

    def count_rows(self, pos: int, n: int) -> None:
        """A row of a decode block was scheduled ``n`` steps from ``pos``."""
        self._count_keys(pos, n)

    def _count_keys(self, pos: int, n: int) -> None:
        """``ds_serve_attn_keys_*``: what ``n`` steps from ``pos`` attend,
        and what ``flash_decode_paged`` fetches for it by its own rule."""
        if not self._reg.enabled:
            return
        p = np.arange(pos, pos + n)
        self._m["ds_serve_attn_keys_attended_total"].inc(int((p + 1).sum()))
        self._m["ds_serve_attn_keys_fetched_total"].inc(int(
            paged_keys_fetched(p, self._page, self.cfg.head_dim).sum()))

    def count_iteration(self, pool) -> None:
        """A scheduler iteration ended."""

    def count_block(self, counts: List[np.ndarray]) -> List[np.ndarray]:
        """The counts a decode block's program returned reached the host:
        take this kind's off the end, return the routing's."""
        return counts

    def page_gauges(self, pool) -> None:
        if self.pages_by_kind and self._reg.enabled:
            for kind, n in pool.pages_used_by_kind().items():
                self._pages_kind[kind].set(n)


class WindowSummaryPages(FullPages):
    """EVA attention: window pages reused in place, then the pages of the
    chunk summaries, both out of one pool and in the arrays ``k`` and ``v``;
    the chunk program's view is all of them, as for full pages."""

    what = "attention='eva'"
    _pages = ("pages as the K and V of a token prefix, which a window page "
              "is not (it is overwritten every eva_window tokens)")
    cannot = {
        "handoff": "serving/handoff.py ships " + _pages,
        "kv_host_tier_pages": "serving/host_tier.py demotes and promotes "
                              + _pages,
        "prefix_caching": "serving/prefix_cache.py shares " + _pages,
        "quantize_kv_cache":
            "models/decoding.py scales int8 rows a position at a time, and "
            "summary rows are pooled from window rows with no scales of "
            "their own",
    }
    counters = {
        **FullPages.counters,
        "ds_serve_eva_window_closes_total":
            "windows closed (pooled into summary rows), by prefill chunks and "
            "decode steps",
        "ds_serve_eva_window_rows_total":
            "window rows attended by live decode rows, summed over steps",
        "ds_serve_eva_summary_rows_total":
            "summary rows attended by live decode rows, summed over steps",
        "ds_serve_eva_prefill_scores_total":
            "scores (query, key row) the two masks keep for the real tokens "
            "of the prefill chunks, one head of one layer",
        "ds_serve_eva_prefill_scores_visited_total":
            "scores the chunk programs' attention computes for those chunks "
            "(eva_chunk_schedule: the kernel's strips, or the whole bucket x "
            "view where the dense form runs), one head of one layer",
    }
    pages_by_kind = True

    def pool_args(self, dtype):
        return {"window_tokens": self.cfg.eva_window,
                "chunk_tokens": self.cfg.eva_chunk}

    def layout(self, pool, num_slots):
        return (super().layout(pool, num_slots)
                + f" ({pool.window_pages} window + {pool.summary_pages} "
                  "summary pages a slot)")

    def check_prefill_chunk(self, prefill_chunk):
        W = self.cfg.eva_window
        if W % prefill_chunk or prefill_chunk & (prefill_chunk - 1):
            raise ValueError(
                f"prefill_chunk={prefill_chunk} must be a power of two that "
                f"divides eva_window={W}: a prefill chunk may not straddle a "
                f"window boundary")

    def count_chunk(self, pool, cache, off, c, cb):
        """``ds_serve_eva_prefill_scores*``: what the masks keep of the
        chunk's scores and what its program's attention computes, from the
        schedule the kernel takes its bounds from; and the window a chunk
        closes."""
        cfg = self.cfg
        if (off + c) % cfg.eva_window == 0:
            self._m["ds_serve_eva_window_closes_total"].inc()
        if not self._reg.enabled:
            return
        sch = eva_chunk_schedule(
            off, cb, real=c, window=cfg.eva_window, chunk=cfg.eva_chunk,
            rows=pool.slot_pages * pool.page, head_dim=cfg.head_dim,
            itemsize=cache["k"].dtype.itemsize)
        self._m["ds_serve_eva_prefill_scores_total"].inc(sch["kept"])
        self._m["ds_serve_eva_prefill_scores_visited_total"].inc(
            sch["visited"])

    def count_rows(self, pos, n):
        """``ds_serve_eva_*``: the rows each step attends (``pos % W + 1``
        window rows and the ``W/C`` summaries of each closed window) and the
        windows the steps close; ``ds_serve_attn_keys_*``: the two together,
        and what ``eva_decode_paged`` fetches for them by its own rule.  An
        EOS row that stops early is counted to its bound."""
        if not self._reg.enabled:
            return
        cfg = self.cfg
        W, C = cfg.eva_window, cfg.eva_chunk
        p = np.arange(pos, pos + n)
        m = self._m
        window, summary = int((p % W + 1).sum()), int((p // W).sum()) * (W // C)
        m["ds_serve_eva_window_rows_total"].inc(window)
        m["ds_serve_eva_summary_rows_total"].inc(summary)
        m["ds_serve_eva_window_closes_total"].inc(
            int(((p + 1) % W == 0).sum()))
        m["ds_serve_attn_keys_attended_total"].inc(window + summary)
        m["ds_serve_attn_keys_fetched_total"].inc(int(
            eva_keys_fetched(p, self._page, cfg.head_dim, W, C).sum()))


class TwoBudgets(FullPages):
    """Sliding and global layers: the table's first ``sliding_window /
    page`` columns name a ring in the window budget's arrays ``k_win`` /
    ``v_win``, the rest full pages in ``k_full`` / ``v_full``."""

    what = "layer_types"
    _pages = ("pages as the K and V of a token prefix in every layer, which "
              "a sliding layer's ring page is not (it is overwritten every "
              "sliding_window tokens)")
    cannot = {
        "handoff": "serving/handoff.py ships " + _pages,
        "kv_host_tier_pages": "serving/host_tier.py demotes and promotes "
                              + _pages,
        "prefix_caching": "serving/prefix_cache.py shares " + _pages,
        "quantize_kv_cache":
            "the int8 cache of models/decoding.py is one array a layer kind "
            "with scales, and the fused decode path (the only one built for "
            "this model) reads no int8 rows",
        "use_fused_decode":
            "the decode step over two page budgets is built on the fused "
            "path only (models/afmoe.py:fused_layers)",
    }
    counters = {
        "ds_serve_attn_window_rows_total":
            "K/V rows the live decode queries attended in ONE sliding layer "
            "(min(pos + 1, window) a step), summed over rows and steps",
        "ds_serve_attn_full_rows_total":
            "K/V rows the live decode queries attended in ONE global layer "
            "(pos + 1 a step), summed over rows and steps",
        "ds_serve_kv_page_steps_total":
            "page x layer x iterations the two budgets held: window pages "
            "times the sliding layers plus full pages times the global "
            "layers, summed over scheduler iterations (by budget at an "
            "instant: "
            "ds_serve_kv_pages_used_by_kind)",
        "ds_serve_kv_page_steps_one_budget_total":
            "page x layer x iterations one budget a layer would have held for "
            "the same positions (every layer a page per kv_page_tokens)",
    }
    takes_valid_len = True          # a ring takes no pad row
    pages_by_kind = True
    page_arrays = ("k_win", "v_win", "k_full", "v_full")
    layer_arrays = ("k_win", "k_full")

    def pool_args(self, dtype):
        return {"ring_tokens": self.cfg.sliding_window}

    def init_cache(self, pool, num_slots, dtype, quantized):
        cfg = self.cfg
        ls, lf = afmoe.kind_layers(cfg)
        z = lambda n, pages: jnp.zeros(
            (n, pages, cfg.num_kv_heads, pool.page, cfg.head_dim), dtype)
        return {"k_win": z(len(ls), pool.num_window_pages),
                "v_win": z(len(ls), pool.num_window_pages),
                "k_full": z(len(lf), pool.num_pages),
                "v_full": z(len(lf), pool.num_pages)}

    def layout(self, pool, num_slots):
        return (f"two page budgets: {pool.num_window_pages - 1} window + "
                f"{pool.num_pages - 1} full x {pool.page}-token pages, "
                f"{num_slots} slots x ({pool.window_pages} ring pages + "
                f"{pool.cache_len} positions)")

    def check_prefill_chunk(self, prefill_chunk):
        W = self.cfg.sliding_window
        if W and prefill_chunk > W:
            raise ValueError(
                f"prefill_chunk={prefill_chunk} exceeds sliding_window={W}: "
                f"a chunk's real rows must be distinct rows of the ring "
                f"(serving/paged_kv.py)")

    def _columns(self, cache, pt_row):
        """(ring pages, full pages) of a slot's table row."""
        wp = self.cfg.sliding_window // cache["k_win"].shape[3]
        return wp, pt_row.shape[0] - wp

    def view(self, cache, pt_row, slot, start, cb):
        """The views ``afmoe.cached_layers`` takes: the ring as the earlier
        chunks left it (the forward attends it BEFORE it appends) and every
        full page."""
        wp, fp = self._columns(cache, pt_row)
        cols = {"win": range(wp), "full": range(wp, wp + fp)}
        return {k: _slot_view(cache[k], pt_row, cols[k[2:]])
                for k in ("k_win", "v_win", "k_full", "v_full")}

    def write_back(self, cache, sub, pt_row, slot, start, cb):
        """All of the ring's pages go back, and of the full ones only those
        the chunk's ``cb`` rows can have touched."""
        wp, fp = self._columns(cache, pt_row)
        spans = {"win": (0, list(range(wp))),
                 "full": (wp, _touched(start, cb, cache["k_full"].shape[3],
                                       fp))}
        return {k: _slot_write_back(cache[k], sub[k], pt_row, *spans[k[2:]])
                for k in cache}

    def count_rows(self, pos, n):
        """``ds_serve_attn_*_rows_total``: the K/V rows each step attends in
        one sliding layer (``min(p + 1, W)``) and in one global layer (``p +
        1``)."""
        if not self._reg.enabled:
            return
        p = np.arange(pos, pos + n) + 1
        self._m["ds_serve_attn_window_rows_total"].inc(
            int(np.minimum(p, self.cfg.sliding_window).sum()))
        self._m["ds_serve_attn_full_rows_total"].inc(int(p.sum()))

    def count_iteration(self, pool):
        """``ds_serve_kv_page_steps_*``: what the two budgets hold, each
        page weighted by the layers of its kind, and what ONE budget would
        hold for the same slots (their full pages, in every layer)."""
        if not self._reg.enabled:
            return
        n_win, n_full = (len(k) for k in afmoe.kind_layers(self.cfg))
        held = pool.pages_used_by_kind()
        self._m["ds_serve_kv_page_steps_total"].inc(
            held["window"] * n_win + held["full"] * n_full)
        self._m["ds_serve_kv_page_steps_one_budget_total"].inc(
            held["full"] * (n_win + n_full))


class LatentPages(FullPages):
    """Latent-attention layers: ``latent`` pages of one row a position that
    all heads share (the normed latent and the shared key values),
    allocated like full pages and position-pure like them, but no per-head
    K and V arrays."""

    what = "latent_attention layers"
    _rows = ("per-head K and V arrays, and a latent page is one row a "
             "position shared by all heads (ROADMAP R2)")
    cannot = {
        "handoff": "serving/handoff.py ships pages as " + _rows,
        "kv_host_tier_pages": "serving/host_tier.py demotes and promotes "
                              "pages as " + _rows,
        "prefix_caching":
            "serving/prefix_cache.py and the engine's boundary-page copy "
            "share pages as " + _rows,
        "quantize_kv_cache":
            "the int8 cache of models/decoding.py scales per-head K and V "
            "rows; a latent row has no int8 form",
        "use_fused_decode":
            "the decode step over latent pages is built on the fused path "
            "only (models/kda_mla.py:fused_layers)",
    }
    counters = {
        "ds_serve_mla_rows_expanded_total":
            "(latent row, latent layer) pairs the prefill chunk programs "
            "decompressed to per-head keys and values: a chunk expands every "
            "earlier row of its request again, a strip of rows at a time "
            "(ops/pallas/flash_attention.py:mla_chunk_attention; a key block "
            "where models/afmoe.py:attend(expand=) runs in its place)",
        "ds_serve_mla_rows_written_total":
            "(latent row, latent layer) pairs the prefill chunk programs "
            "wrote for the real tokens of their chunks",
    }
    # mla_chunk_attention, dsa_index_scores_chunk and dsa_chunk_attention
    # pad a chunk's queries to one lane tile inside the call
    chunk_rows = _LANES
    page_arrays = ("latent", "index")
    layer_arrays = ("latent",)

    def init_cache(self, pool, num_slots, dtype, quantized):
        cfg = self.cfg
        return {"latent": jnp.zeros(
            (len(kda_mla.kind_layers(cfg)[1]), pool.num_pages, 1, pool.page,
             kda_mla.row_width(cfg)), dtype)}

    def layout(self, pool, num_slots):
        return super().layout(pool, num_slots) + " of latent rows"

    def view(self, cache, pt_row, slot, start, cb):
        """The view ``kda_mla.cached_layers`` takes: every latent page of
        the slot."""
        return {"latent": _slot_view(cache["latent"], pt_row,
                                     range(pt_row.shape[0]))}

    def write_back(self, cache, sub, pt_row, slot, start, cb):
        """Of the latent pages only those the chunk's ``cb`` rows can have
        touched go back."""
        return {"latent": _slot_write_back(
            cache["latent"], sub["latent"], pt_row, 0,
            _touched(start, cb, cache["latent"].shape[3], pt_row.shape[0]))}

    def count_chunk(self, pool, cache, off, c, cb):
        """``ds_serve_mla_rows_*``: the rows the chunk's attention visits
        (``mla_chunk_schedule``: the kernel's strips up to the bucket's end,
        or whole key blocks where ``afmoe.attend`` runs), each decompressed
        once a latent layer, and the real rows it adds."""
        if not self._reg.enabled:
            return
        cfg, (layers, *_, width) = self.cfg, cache["latent"].shape
        self._m["ds_serve_mla_rows_expanded_total"].inc(
            layers * mla_chunk_schedule(
                off, cb, pool.slot_pages * pool.page, heads=cfg.num_heads,
                kv=cfg.mla_kv_rank, nope=cfg.mla_nope_dim,
                rot=cfg.mla_rot_dim, v_dim=cfg.mla_v_dim, row_width=width,
                itemsize=cache["latent"].dtype.itemsize)["visited"])
        self._m["ds_serve_mla_rows_written_total"].inc(layers * c)

    def count_rows(self, pos, n):
        """Nothing: the rows ``mla_decode_paged`` attends are taken from the
        benchmark loop's marks, and its pages are no per-head K/V pages."""


class SlotState:
    """A float32 recurrent ``state`` and a convolution ``tail`` per SLOT for
    the linear-attention layers (``models/kda_mla.py``) or the mamba2 layers
    (``models/ssm_moe.py``), their shapes the model module's
    (``state_shapes``: heads of a square matrix, or a state tile that is
    not), beside whatever pages the class after this one in a kind's bases
    keeps: never allocated or freed (a request's first chunk program reads
    zeros), sliced out by the slot's index for a chunk and put back in
    place."""

    # the row steps are counted by the decode-block program itself (the
    # live mask and the state kernel's grid) and fetched with its tokens
    state_counters = {
        "ds_serve_state_row_steps_total":
            "(live row, linear-attention layer, decode step) triples: the "
            "state updates the decode steps really made",
        "ds_serve_state_row_steps_visited_total":
            "(row, linear-attention layer, decode step) triples whose state "
            "the decode kernel read and wrote, live or parked "
            "(ops/pallas/decode.py:kda_decode_step)",
        "ds_serve_state_resets_total":
            "slot states reset: a request (or a preempted one's resume) took "
            "a slot and its first chunk starts from a zero state",
        "ds_serve_ssm_chunk_rows_total":
            "(row, mamba2 layer) pairs the prefill chunk programs' chunked "
            "scan (models/ssm_moe.py:ssm_chunk_scan) worked, the pad rows of "
            "their buckets included (beside ds_serve_prefill_pad_rows_total)",
        "ds_serve_mamba1_chunk_rows_total":
            "(row, mamba1 layer) pairs the prefill chunk programs' selective "
            "scan (ops/pallas/selective_scan.py:selective_scan_chunk) "
            "walked, the pad rows of their buckets included",
    }
    takes_valid_len = True          # the state is left as of the last real row

    def pool_args(self, dtype):
        return {"slot_state_bytes":
                afmoe.form(self.cfg).slot_state_bytes(self.cfg, dtype)}

    def init_cache(self, pool, num_slots, dtype, quantized):
        state, tail = afmoe.form(self.cfg).state_shapes(self.cfg, num_slots)
        return {**super().init_cache(pool, num_slots, dtype, quantized),
                "state": jnp.zeros(state, jnp.float32),
                "tail": jnp.zeros(tail, dtype)}

    def layout(self, pool, num_slots):
        return (super().layout(pool, num_slots)
                + f", and {pool.state_bytes} bytes of slot state")

    def view(self, cache, pt_row, slot, start, cb):
        """The pages' view, and the slot's state and tail sliced out by its
        index (the chunk carries them through and starts from zeros at
        position 0)."""
        own = lambda v: jax.lax.dynamic_slice_in_dim(v, slot, 1, axis=1)
        return {**super().view(cache, pt_row, slot, start, cb),
                "state": own(cache["state"]), "tail": own(cache["tail"])}

    def write_back(self, cache, sub, pt_row, slot, start, cb):
        """The pages as the kind puts them back; state and tail in place at
        the slot's index."""
        put = lambda k: jax.lax.dynamic_update_slice_in_dim(
            cache[k], sub[k], slot, axis=1)
        return {**super().write_back(cache, sub, pt_row, slot, start, cb),
                "state": put("state"), "tail": put("tail")}

    def attach(self, registry, pool):
        super().attach(registry, pool)
        registry.gauge("ds_serve_state_bytes").set(pool.state_bytes)

    def count_admit(self):
        self._m["ds_serve_state_resets_total"].inc()

    def count_chunk(self, pool, cache, off, c, cb):
        super().count_chunk(pool, cache, off, c, cb)
        for kind, name in (("mamba2", "ds_serve_ssm_chunk_rows_total"),
                           ("mamba1", "ds_serve_mamba1_chunk_rows_total")):
            self._m[name].inc(cb * self.cfg.layer_types.count(kind))

    def count_block(self, counts):
        """``ds_serve_state_row_steps_*``: the block's (row, linear layer)
        pairs, live and visited by the state kernel (the last of
        ``kda_mla.fused_layers``' counts)."""
        steps = counts.pop()
        self._m["ds_serve_state_row_steps_total"].inc(int(steps[0]))
        self._m["ds_serve_state_row_steps_visited_total"].inc(int(steps[1]))
        return counts


class LatentPagesAndState(SlotState, LatentPages):
    """Linear-attention layers beside latent-attention layers: the latent
    pages, and :class:`SlotState`'s state and tail a slot."""

    what = "linear_attention / latent_attention layers"
    cannot = {
        "handoff": "serving/handoff.py ships pages as the K and V of a token "
                   "prefix, and a recurrent state is not a page",
        "kv_host_tier_pages":
            "serving/host_tier.py demotes and promotes pages for the prefix "
            "cache, which is off for this model (a state is not "
            "position-pure)",
        "prefix_caching":
            "serving/prefix_cache.py shares pages as a function of the token "
            "prefix, and a recurrent state is a slot's, not a page's (the "
            "latent pages alone are position-pure, and shared they would "
            "still not be per-head K and V arrays: LatentPages)",
        "quantize_kv_cache":
            "the int8 cache of models/decoding.py scales per-head K and V "
            "rows; a latent row and a float32 state have no int8 form",
        "use_fused_decode":
            "the decode step over latent pages and slot state is built on "
            "the fused path only (models/kda_mla.py:fused_layers)",
    }
    counters = {**LatentPages.counters, **SlotState.state_counters}


class FullPagesAndState(SlotState, FullPages):
    """Linear-attention layers beside per-head ``full_attention`` layers
    (``models/kda_mla.py``): ``k`` and ``v`` pages ``[full layers, pages,
    Hkv, page, Dh]`` for the full layers alone (``cfg.cache_layers``), one
    budget and one table column a page as under :class:`FullPages`, and
    :class:`SlotState`'s state and tail a slot.  The pages are position-pure
    but the state is not, so what rests on a page being a function of the
    token prefix is off or refused until a state can be snapshot at a page
    boundary (ROADMAP R5 / R7)."""

    _state = ("a recurrent state is a slot's, not a page's: the K/V pages of "
              "the full layers alone do not restore a request (state "
              "snapshots at page boundaries: ROADMAP R5 / R7)")
    cannot = {
        "handoff": "serving/handoff.py ships pages as the K and V of a token "
                   "prefix in every layer; " + _state,
        "kv_host_tier_pages":
            "serving/host_tier.py demotes and promotes pages for the prefix "
            "cache, which is off for this model; " + _state,
        "prefix_caching":
            "serving/prefix_cache.py shares pages as a function of the token "
            "prefix; " + _state,
        "quantize_kv_cache":
            "the int8 cache of models/decoding.py is read by the Llama "
            "backbone's decode step, and the fused path of this model "
            "(models/kda_mla.py:fused_layers) reads no int8 rows; a float32 "
            "state has no int8 form",
        "use_fused_decode":
            "the decode step over K/V pages and slot state is built on the "
            "fused path only (models/kda_mla.py:fused_layers)",
    }
    counters = {
        **SlotState.state_counters,
        **FullPages.counters,
        "ds_serve_full_kv_rows_read_total":
            "(live row, full_attention layer, decode step) context rows the "
            "decode attention kernel was asked to read, K and V counted "
            "once: pos + 1 a row a step in each full layer of a model whose "
            "other layers keep a state",
    }
    pages_by_kind = True

    def __init__(self, cfg):
        super().__init__(cfg)
        # the recurrence works blocks of the model module's ``chunk_rows``
        # (a sub-chunk of kda_mla's chunkwise delta rule, a block of
        # ssm_moe's chunked scan), and under one block a chunk program's time
        # is its weights' stream whatever its rows: a smaller bucket saves no
        # work and costs a compile (three of eight chunk programs, 7 s each
        # cold at Solar-Open2's widths)
        self.chunk_rows = afmoe.form(cfg).chunk_rows(cfg)
        # the kinds of layer that keep a cache, as the pattern names them:
        # the state's, then the pages'
        self.what = " / ".join(
            [k for k in dict.fromkeys(cfg.layer_types)
             if k not in ("experts", "mlp", "full_attention")]
            + ["full_attention"]) + " layers"

    def layout(self, pool, num_slots):
        return (super().layout(pool, num_slots)
                + f" (pages in {self.cfg.cache_layers} of "
                  f"{self.cfg.num_layers} layers)")

    def count_rows(self, pos, n):
        """``ds_serve_full_kv_rows_read_total``: ``p + 1`` rows a step in
        each full layer."""
        if not self._reg.enabled:
            return
        p = np.arange(pos, pos + n) + 1
        self._m["ds_serve_full_kv_rows_read_total"].inc(
            int(p.sum()) * self.cfg.cache_layers)
        self._count_keys(pos, n)

    def page_gauges(self, pool):
        """Every page is a full layer's (the state is no page:
        ``ds_serve_state_bytes``)."""
        if self._reg.enabled:
            self._pages_kind["full"].set(pool.pages_used)


class IndexedLatentPagesAndRing(LatentPages):
    """Latent layers under a learned selection of their keys beside sliding
    latent layers (``models/kda_mla.py``): ``latent`` pages and, under the
    same table, ``index`` pages of one index key a position ([latent layers,
    pages, 1, page, index size]); per SLOT a ``ring`` of the sliding layers'
    rows ([sliding layers, slots, ring rows, their row width], a row of
    position p at ``p % ring rows``, masked by the position it holds), never
    allocated or freed: a request reads no row of it that it has not written
    (a ring row before the sequence's start, or older than the window, is
    masked by position)."""

    what = "latent_attention layers under an indexer / " \
           "latent_sliding_attention layers"
    _not = ("a position here is a latent row AND an index key, and the "
            "sliding layers' ring is a slot's, not a page's (ROADMAP R10)")
    cannot = {
        "handoff": "serving/handoff.py ships pages as per-head K and V; "
                   + _not,
        "kv_host_tier_pages": "serving/host_tier.py demotes and promotes "
                              "pages as per-head K and V; " + _not,
        "prefix_caching": "serving/prefix_cache.py shares pages as per-head "
                          "K and V of a token prefix; " + _not,
        "quantize_kv_cache":
            "the int8 cache of models/decoding.py scales per-head K and V "
            "rows; a latent row and an index key have no int8 form (the "
            "release's FP8 index keys are left out)",
        "use_fused_decode":
            "the decode step over index keys, selected rows and rings is "
            "built on the fused path only (models/kda_mla.py:fused_layers)",
    }
    counters = {
        **LatentPages.counters,
        "ds_serve_dsa_keys_scored_total":
            "index keys the live decode rows scored in ONE indexed layer "
            "(pos + 1 a step), summed over rows and steps",
        "ds_serve_dsa_keys_attended_total":
            "keys the live decode rows attended in ONE indexed layer "
            "(min(pos + 1, mla_index_topk) a step), summed over rows and "
            "steps",
        "ds_serve_dsa_chunk_keys_scored_total":
            "index keys the real tokens of the prefill chunks scored in ONE "
            "indexed layer (t + 1 for the token at t)",
        "ds_serve_dsa_chunk_keys_attended_total":
            "keys the real tokens of the prefill chunks attended in ONE "
            "indexed layer (min(t + 1, mla_index_topk))",
        "ds_serve_dsa_rows_sorted_total":
            "(row, step) pairs the decode steps' top-k sorted in ONE indexed "
            "layer: the live rows in groups of kda_mla.SORT_GROUP, the rows "
            "that pad a step's last group included (counted on the device)",
        "ds_serve_dsa_rows_gathered_total":
            "(row, step) pairs whose selected rows the decode steps looked "
            "up, gathered and attended in ONE indexed layer: the live rows "
            "in groups of ops/pallas/decode.py:GATHER_GROUP, padding "
            "included; ds_serve_decode_tokens_total over it is how full "
            "the groups are (dsa_select_rows_live_share in BENCHMARK.json)",
        "ds_serve_attn_window_rows_total":
            TwoBudgets.counters["ds_serve_attn_window_rows_total"],
    }
    takes_valid_len = True          # a ring takes no pad row
    pages_by_kind = True

    def _ring(self, pool):
        """(sliding layers, ring rows, a ring row's width)."""
        cfg = self.cfg
        kd = cfg.mla_kind("latent_sliding_attention")
        return (len(kda_mla.sliding_layers(cfg)),
                kda_mla.ring_rows(cfg, pool.page), kd.row_width)

    def pool_args(self, dtype):
        cfg = self.cfg
        if not kda_mla.sliding_layers(cfg):
            return {}
        # the pool's page is not known yet: the ring's bytes are reported
        # for rows of the window itself
        kd = cfg.mla_kind("latent_sliding_attention")
        return {"slot_state_bytes": len(kda_mla.sliding_layers(cfg))
                * cfg.sliding_window * kd.row_width
                * jnp.dtype(dtype).itemsize}

    def init_cache(self, pool, num_slots, dtype, quantized):
        cfg = self.cfg
        out = super().init_cache(pool, num_slots, dtype, quantized)
        kd = cfg.mla_kind("latent_attention")
        if kd.index:
            out["index"] = jnp.zeros(out["latent"].shape[:-1]
                                     + (kd.index[1],), dtype)
        if kda_mla.sliding_layers(cfg):
            n, rows, width = self._ring(pool)
            out["ring"] = jnp.zeros((n, num_slots, rows, width), dtype)
        return out

    def layout(self, pool, num_slots):
        return (super().layout(pool, num_slots) + " and index keys, and "
                f"rings of {self._ring(pool)[1]} rows a slot in "
                f"{self._ring(pool)[0]} sliding layers")

    def check_prefill_chunk(self, prefill_chunk):
        pass        # a chunk longer than the ring leaves its last rows there

    def view(self, cache, pt_row, slot, start, cb):
        """Every latent and index page of the slot, and the slot's rings
        sliced out by its index."""
        cols = range(pt_row.shape[0])
        out = {k: _slot_view(cache[k], pt_row, cols)
               for k in ("latent", "index") if k in cache}
        if "ring" in cache:
            out["ring"] = jax.lax.dynamic_slice_in_dim(cache["ring"], slot, 1,
                                                       axis=1)
        return out

    def write_back(self, cache, sub, pt_row, slot, start, cb):
        """Of the pages only those the chunk's ``cb`` rows can have touched
        go back; the rings in place at the slot's index."""
        page = cache["latent"].shape[3]
        pages = _touched(start, cb, page, pt_row.shape[0])
        out = {k: _slot_write_back(cache[k], sub[k], pt_row, 0, pages)
               for k in ("latent", "index") if k in cache}
        if "ring" in cache:
            out["ring"] = jax.lax.dynamic_update_slice_in_dim(
                cache["ring"], sub["ring"], slot, axis=1)
        return out

    def attach(self, registry, pool):
        super().attach(registry, pool)
        registry.gauge("ds_serve_state_bytes").set(pool.state_bytes)

    def count_chunk(self, pool, cache, off, c, cb):
        """The rows the chunk's attention decompresses (an indexed layer:
        every row up to the bucket's end, whole blocks; a sliding layer: the
        window before the chunk and the bucket), the rows it writes, and
        the keys its real tokens score and attend in one indexed layer."""
        if not self._reg.enabled:
            return
        from deepspeed_tpu.ops.pallas.flash_attention import _DSA_BLOCK_K

        cfg = self.cfg
        n_lat = cache["latent"].shape[0]
        n_sw = len(kda_mla.sliding_layers(cfg))
        m = self._m
        m["ds_serve_mla_rows_expanded_total"].inc(
            n_lat * -(-(off + cb) // _DSA_BLOCK_K) * _DSA_BLOCK_K
            + n_sw * (cb + max(cfg.sliding_window - 1, 0)))
        m["ds_serve_mla_rows_written_total"].inc((n_lat + n_sw) * c)
        t = np.arange(off, off + c) + 1
        m["ds_serve_dsa_chunk_keys_scored_total"].inc(int(t.sum()))
        m["ds_serve_dsa_chunk_keys_attended_total"].inc(
            int(np.minimum(t, cfg.mla_index_topk or t.max()).sum()))

    def count_rows(self, pos, n):
        """``ds_serve_dsa_keys_*`` and ``ds_serve_attn_window_rows_total``:
        what each step scores and attends in one indexed layer, and attends
        in one sliding layer."""
        if not self._reg.enabled:
            return
        cfg = self.cfg
        p = np.arange(pos, pos + n) + 1
        m = self._m
        m["ds_serve_dsa_keys_scored_total"].inc(int(p.sum()))
        m["ds_serve_dsa_keys_attended_total"].inc(
            int(np.minimum(p, cfg.mla_index_topk or p.max()).sum()))
        if cfg.sliding_window:
            m["ds_serve_attn_window_rows_total"].inc(
                int(np.minimum(p, cfg.sliding_window).sum()))

    def count_block(self, counts):
        """``ds_serve_dsa_rows_*``: the (row, step) pairs the block's
        selection sorted and gathered in one indexed layer (the last of
        ``kda_mla.fused_layers``' counts, where the model has an indexer)."""
        if self.cfg.mla_index_topk:
            rows = counts.pop()
            self._m["ds_serve_dsa_rows_sorted_total"].inc(int(rows[0]))
            self._m["ds_serve_dsa_rows_gathered_total"].inc(int(rows[1]))
        return counts

    def page_gauges(self, pool):
        """The three budgets: the rings of the slots that hold pages (as
        pages), the latent pages, and the same pages' index keys."""
        if not self._reg.enabled:
            return
        held = [pool.slot_pages_used(s) for s in range(pool.num_slots)]
        ring = self._ring(pool)[1] // pool.page \
            if kda_mla.sliding_layers(self.cfg) else 0
        self._pages_kind["window"].set(ring * sum(n > 0 for n in held))
        self._pages_kind["full"].set(sum(held))
        self._pages_kind["index"].set(
            sum(held) if self.cfg.mla_index_topk else 0)


KINDS = (FullPages, WindowSummaryPages, TwoBudgets, LatentPages,
         LatentPagesAndState, IndexedLatentPagesAndRing, FullPagesAndState)


def cache_kind(cfg) -> FullPages:
    """The kind of cache a slot of a model of configuration ``cfg`` has."""
    if getattr(cfg, "is_eva", False):
        return WindowSummaryPages(cfg)
    if getattr(cfg, "is_kda_mla", False):
        if cfg.mla_index_topk or kda_mla.sliding_layers(cfg):
            return IndexedLatentPagesAndRing(cfg)
        if kda_mla.full_layers(cfg):
            return FullPagesAndState(cfg)
        return (LatentPagesAndState if kda_mla.kind_layers(cfg)[0]
                else LatentPages)(cfg)
    if getattr(cfg, "is_mixer", False):
        return FullPagesAndState(cfg)
    if getattr(cfg, "is_afmoe", False):
        return TwoBudgets(cfg)
    return FullPages(cfg)
