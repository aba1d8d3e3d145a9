"""The contract of ``serving/cache_kind.py``, once over the seven kinds of
slot cache at the tiny sizes their model tests build: full pages (a tiny
Llama; and a LOOPED stack, two layers run three times with post-norms,
whose pool is the same kind over ``cache_layers`` = 6 layers), window +
summary pages (``test_evabyte``), two page budgets
(``test_trinity``), latent pages alone (``test_axk1``), latent pages + slot
state (``test_kimi_linear``), latent pages + index keys + slot rings
(``test_dots3_note``), K/V pages in the full layers only + slot state
(``test_solar_open2``; and, the state's shape the model module's,
``test_nemotron3_nano``).

What every kind owes the engine: a slot's view written back unchanged leaves
the pool as it was, and a changed one touches nobody else's pages; an
aborted request returns every page; a reused slot serves what a fresh engine
serves; what a kind cannot be served with is refused by its reason, and the
position-pure kind refuses nothing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.mesh import build_mesh
from deepspeed_tpu.models import CausalLM, ModelConfig, causal_lm
from deepspeed_tpu.serving.cache_kind import (KINDS, FullPages,
                                              FullPagesAndState,
                                              IndexedLatentPagesAndRing,
                                              LatentPages,
                                              LatentPagesAndState, TwoBudgets,
                                              WindowSummaryPages, cache_kind)
from deepspeed_tpu.serving.paged_kv import PagedKVPool

from ._serving import as_found, with_noise
from . import (test_axk1, test_dots3_note, test_evabyte, test_kimi_linear,
               test_nemotron3_nano, test_solar_open2, test_trinity)

ENGINE = dict(num_slots=3, prefill_chunk=16, max_prefill_chunks=2,
              decode_block_tokens=4, max_out_tokens=96, kv_page_tokens=8,
              dtype="float32")
# kind -> (its class, its model's fields | None for the tiny Llama, engine)
CASES = {
    "full": (FullPages, None, ENGINE),
    "eva": (WindowSummaryPages, test_evabyte.TINY, test_evabyte.ENGINE),
    "two_budgets": (TwoBudgets, test_trinity.FIELDS, test_trinity.ENGINE),
    "latent": (LatentPages, test_axk1.FIELDS, test_axk1.ENGINE),
    "state": (LatentPagesAndState, test_kimi_linear.FIELDS,
              test_kimi_linear.ENGINE),
    "indexed": (IndexedLatentPagesAndRing, test_dots3_note.FIELDS,
                test_dots3_note.ENGINE),
    "hybrid": (FullPagesAndState, test_solar_open2.FIELDS,
               test_solar_open2.ENGINE),
    # the same kind under the one-mixer form: a state that is no square
    "mixer": (FullPagesAndState, test_nemotron3_nano.FIELDS,
              test_nemotron3_nano.ENGINE),
    # no kind of its own: full pages, one layer a (pass, layer) pair
    "looped": (FullPages, dict(
        vocab_size=96, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, max_seq_len=2048, total_ut_steps=3,
        sandwich_norm=True, loop_exit_gate=True), ENGINE),
}
BY_SLOT = ("state", "tail", "ring")     # entries [layers, slots, ...]
NAMES = list(CASES)
# the kinds with a table of what they cannot be served with
REFUSING = [n for n in NAMES if CASES[n][0] is not FullPages]


@pytest.fixture(scope="module")
def built():
    """name -> (model, params), built once a module and on demand."""
    mesh = build_mesh(devices=jax.devices()[:1])
    made = {}

    def get(name):
        if name not in made:
            fields = CASES[name][1]
            if fields is None:
                model = causal_lm("llama-tiny", mesh=mesh, num_layers=2,
                                  hidden_size=64, intermediate_size=128,
                                  num_heads=4, num_kv_heads=2, vocab_size=96,
                                  remat=False)
            else:
                model = CausalLM(ModelConfig(**fields), mesh)
            made[name] = model, with_noise(        # no gain of exactly 1
                model.init(jax.random.PRNGKey(0)))
        return made[name]

    return get


def serve_of(built, name, **kw):
    model, params = built(name)
    role = {k: kw.pop(k) for k in ("role",) if k in kw}
    return deepspeed_tpu.init_serving(
        model, config=dict(CASES[name][2], **kw), params=params,
        mesh=model.mesh, **role)


@pytest.fixture(scope="module")
def engines(built):
    """name -> one engine of that kind at its ``CASES`` settings, built on
    demand, for the cases that differ in their requests alone."""
    made = {}

    def get(name):
        if name not in made:
            made[name] = serve_of(built, name)
        return made[name]

    yield get
    for serve in made.values():
        serve.close()


def prompts_of(built, name, lengths, seed):
    vocab = built(name)[0].config.vocab_size
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n) for n in lengths]


@pytest.mark.parametrize("name", NAMES)
def test_the_chooser_picks_the_kind(built, name):
    kind = cache_kind(built(name)[0].config)
    assert type(kind) is CASES[name][0] and type(kind) in KINDS
    assert bool(kind.cannot) == (name in REFUSING)
    if name == "looped":
        cfg = built(name)[0].config
        assert (cfg.cache_layers, cfg.num_layers) == (6, 2)
        pool = PagedKVPool(3, 96, page_tokens=8)
        assert {v.shape for v in kind.init_cache(
            pool, 3, jnp.float32, False).values()} == {
                (6, pool.num_pages, 2, 8, 16)}


@pytest.mark.parametrize("name", NAMES)
def test_a_view_written_back_leaves_the_pool_and_a_changed_one_its_neighbours(
        built, name):
    """Slot 1's pages lie out of order between two other slots' in a pool of
    random rows, its unallocated columns all naming junk page 0.  Its view
    written back as it came leaves every array bit-identical; with every
    value of the view changed, the page under the chunk's start changes and
    no page (and no state) of another slot does."""
    cfg = built(name)[0].config
    engine = CASES[name][2]
    kind = cache_kind(cfg)
    pool = PagedKVPool(3, engine["max_out_tokens"], page_tokens=8,
                       **kind.pool_args(jnp.float32))
    for slot, tokens in ((1, 8), (0, 16), (1, 16), (2, 8), (1, 24)):
        assert pool.ensure(slot, tokens)
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 8))
    cache = {k: jax.random.uniform(next(keys), v.shape, jnp.float32, 0.5, 1.5)
             for k, v in kind.init_cache(pool, 3, jnp.float32, False).items()}
    row, slot, start, cb = jnp.asarray(pool.page_table[1]), 1, 8, 16

    @jax.jit
    def there_and_back(cache, add):
        sub = kind.view(cache, row, slot, start, cb)
        sub = {k: v + add for k, v in sub.items()}
        return kind.write_back(cache, sub, row, slot, start, cb)

    same = there_and_back(cache, 0.0)
    assert set(same) == set(cache)
    for k in cache:
        np.testing.assert_array_equal(np.asarray(same[k]),
                                      np.asarray(cache[k]), err_msg=k)
    changed = there_and_back(cache, 1.0)
    for k, before in cache.items():
        before, after = np.asarray(before), np.asarray(changed[k])
        if k in BY_SLOT:                        # [layers, slots, ...]
            mine = [slot]
        elif k.endswith("_win"):
            mine = pool._owned_win[slot]
        else:
            mine = pool._owned[slot]
        others = [i for i in range(before.shape[1]) if i not in mine
                  and (i != 0 or k in BY_SLOT)]
        np.testing.assert_array_equal(after[:, others], before[:, others],
                                      err_msg=k)
        # the slot's own state; a ring page; the page of position ``start``
        under_start = (slot if k in BY_SLOT else
                       mine[0] if k.endswith("_win") else mine[start // 8])
        assert (after[:, under_start] == before[:, under_start] + 1.0).all()


@pytest.mark.parametrize("name", NAMES)
def test_an_aborted_request_returns_every_page(built, engines, name):
    """A request aborted mid-flight, after chunks and decode blocks have
    given it pages of every kind the slot holds, returns them all."""
    long, short = prompts_of(built, name, (70 if name == "eva" else 40, 12),
                             seed=5)
    with as_found(engines(name)) as serve:
        req = serve.submit(long, max_new_tokens=50)
        other = serve.submit(short, max_new_tokens=8)
        for _ in range(6):
            serve.step()
        held = serve.pool.pages_used_by_kind()
        assert serve.pool.slot_pages_used(req.slot) > 1 and not req.done
        if name == "eva":
            assert held["summary"] > 0
        if name == "two_budgets":
            assert held["window"] > 0 and held["full"] > 0
        serve.abort(req)
        serve.run()
    assert req.done and req.finish_reason == "cancelled"
    assert other.done and len(other.output_tokens) == 8
    assert serve.pool.pages_used == 0


@pytest.mark.parametrize("name", NAMES)
def test_a_reused_slot_serves_what_a_fresh_engine_serves(built, engines,
                                                         name):
    """Five requests over three slots: the fourth and fifth take slots a
    finished request left its rows (and its state) in, and are served what
    a fresh engine serves them in slots nothing was in."""
    prompts = prompts_of(built, name, (20, 33, 9, 25, 18), seed=2)
    with as_found(engines(name)) as serve:
        reqs = [serve.submit(p, max_new_tokens=12) for p in prompts]
        serve.run()
    fresh = serve_of(built, name)
    want = [fresh.submit(p, max_new_tokens=12) for p in prompts[3:]]
    fresh.run()
    for r, w in zip(reqs[3:], want):
        assert list(r.output_tokens) == list(w.output_tokens)
    fresh.close()


def test_two_budgets_preempt_and_resume_are_token_identical(built, engines):
    """A full budget of 13 pages for three slots: the youngest is preempted,
    gives back its ring and its full pages, re-prefills prompt + outputs
    through both budgets, and every request still gets the tokens an
    unpressed engine gives it."""
    prompts = prompts_of(built, "two_budgets", (30, 41, 22), seed=3)
    news = (40, 30, 50)
    tight = serve_of(built, "two_budgets", kv_pool_tokens=104)
    with as_found(engines("two_budgets")) as easy:
        want = [easy.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, news)]
        easy.run()
    got = [tight.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    tight.run()
    tight.pool.check_no_leak()
    assert tight.pool.pages_used == 0
    assert sum(r.preemptions for r in got) > 0
    for w, g in zip(want, got):
        assert list(g.output_tokens) == list(w.output_tokens)
    tight.close()


# -- the refusal table --------------------------------------------------------
# option of ``cannot`` -> how an engine is asked for it.  The per-model tests
# hold the rows their model lists, by the substring each names; here the rows
# they leave out, and the kind that lists none.
ASKED = {
    "handoff": dict(role="decode"),
    "kv_host_tier_pages": dict(kv_host_tier_pages=4),
    "quantize_kv_cache": dict(quantize_kv_cache=True),
    "use_fused_decode": dict(use_fused_decode=False),
}


@pytest.mark.parametrize("name", REFUSING)
def test_prefill_only_is_refused_with_the_kinds_reason(engines, name):
    with as_found(engines(name)) as serve:
        kind = serve.kind
        with pytest.raises(NotImplementedError) as err:
            serve.submit([1, 2, 3], prefill_only=True)
    assert str(err.value) == (f"prefill_only with {kind.what}: "
                              f"{kind.cannot['handoff']}")
    assert "handoff.py" in str(err.value)


@pytest.mark.parametrize("name", ["two_budgets", "latent", "state",
                                  "indexed", "hybrid"])
def test_the_decode_role_is_refused_with_the_kinds_reason(built, name):
    with pytest.raises(NotImplementedError) as err:
        serve_of(built, name, **ASKED["handoff"])
    kind = cache_kind(built(name)[0].config)
    assert str(err.value) == f"role='decode' with {kind.what}: " \
                             f"{kind.cannot['handoff']}"


@pytest.mark.parametrize("name", ["full", "looped"])
@pytest.mark.parametrize("option", [*ASKED, "prefill_only"])
def test_full_pages_refuse_nothing(built, option, name):
    """Every option another kind's table lists builds (and ``prefill_only``
    submits) on position-pure pages, whatever the number of cache layers
    under the table; prefix caching stays on."""
    serve = serve_of(built, name, **ASKED.get(option, {}))
    assert serve.kind.cannot == {} and serve.prefix_cache is not None
    assert (serve.host_store is not None) == (option == "kv_host_tier_pages")
    if option == "prefill_only":
        req = serve.submit(np.arange(20), prefill_only=True)
        serve.run()
        assert req.finish_reason == "prefill_done"
        assert len(req.handoff) == 20 // serve.pool.page
    serve.close()


@pytest.mark.parametrize("value", [False, True])
def test_the_removed_paged_kv_cache_option(built, value):
    """``paged_kv_cache`` is no field any more, so the config would let it
    pass as an unknown key: ``False`` is refused by name (it would be served
    from the pool unasked), ``True`` asks for what every engine does."""
    if value:
        serve = serve_of(built, "full", paged_kv_cache=True)
        assert serve.pool is not None
        serve.close()
    else:
        with pytest.raises(ValueError, match="paged_kv_cache=False.*removed"):
            serve_of(built, "full", paged_kv_cache=False)


# -- the sixth kind: index keys under the latent pages' table, rings by slot --
def test_the_indexed_kinds_arrays_and_pool_arguments(built):
    cfg = built("indexed")[0].config
    kind = cache_kind(cfg)
    # three sliding layers' windows of 13 rows of 128 values, float32
    assert kind.pool_args(jnp.float32) == {
        "slot_state_bytes": 3 * 13 * 128 * 4}
    pool = PagedKVPool(3, 96, page_tokens=8, **kind.pool_args(jnp.float32))
    cache = kind.init_cache(pool, 3, jnp.float32, False)
    assert {k: v.shape for k, v in cache.items()} == {
        "latent": (2, pool.num_pages, 1, 8, 128),
        "index": (2, pool.num_pages, 1, 8, 16),
        "ring": (3, 3, 16, 128)}            # 13 rows in whole pages of 8
    assert kind.takes_valid_len and kind.pages_by_kind
    assert "rings of 16 rows a slot in 3 sliding layers" in kind.layout(pool,
                                                                         3)


@pytest.mark.parametrize("option", [*ASKED, "prefix_caching"])
def test_the_indexed_kind_refuses_by_its_own_reasons(built, engines, option):
    kind = cache_kind(built("indexed")[0].config)
    assert set(kind.cannot) == {*ASKED, "prefix_caching"}
    if option == "prefix_caching":          # turned off, with the reason
        serve = engines("indexed")
        assert serve.prefix_cache is None and "index key" in kind.cannot[option]
        return
    with pytest.raises(NotImplementedError) as err:
        serve_of(built, "indexed", **ASKED[option])
    assert kind.cannot[option] in str(err.value)


def test_the_indexed_kinds_counters_follow_the_positions(built):
    """Served with the registry on: the decode rows' keys scored and attended
    (``pos + 1`` and ``min(pos + 1, 16)`` a step), the chunks' pair of the
    same, the window rows of one sliding layer, and the three budgets."""
    from deepspeed_tpu.monitor.metrics import MetricsRegistry

    model, params = built("indexed")
    reg = MetricsRegistry().enable()
    serve = deepspeed_tpu.init_serving(
        model, config=dict(CASES["indexed"][2]), params=params,
        mesh=model.mesh, registry=reg)
    prompt, n_out = 37, 12
    serve.submit(np.arange(prompt) % 96, max_new_tokens=n_out)
    for _ in range(2):
        serve.step()
    serve.kind.page_gauges(serve.pool)
    held = {k: reg.get("ds_serve_kv_pages_used_by_kind", {"kind": k}).value
            for k in ("window", "full", "index")}
    serve.run()
    value = lambda name: reg.get(name).value
    t = np.arange(prompt) + 1
    assert value("ds_serve_dsa_chunk_keys_scored_total") == t.sum()
    assert value("ds_serve_dsa_chunk_keys_attended_total") == \
        np.minimum(t, 16).sum()
    # the first token comes from the last chunk; the steps run from there
    p = np.arange(prompt, prompt + n_out - 1) + 1
    assert value("ds_serve_dsa_keys_scored_total") == p.sum()
    assert value("ds_serve_dsa_keys_attended_total") == 16 * len(p)
    assert value("ds_serve_attn_window_rows_total") == 13 * len(p)
    assert value("ds_serve_mla_rows_written_total") == 5 * prompt
    # one busy slot: its rings as two pages, its latent pages, their keys
    assert held["window"] == 2 and held["full"] == held["index"] >= 5
    serve.close()


@pytest.mark.parametrize("name", NAMES)
def test_the_keys_a_paged_decode_step_fetches_are_counted_by_its_rule(built,
                                                                      name):
    """``ds_serve_attn_keys_*``: the kinds whose pages are per-head K and V
    rows under ONE table (full pages, a looped stack's, the hybrid's full
    layers) count ``pos + 1`` keys a step attended and what the kernel's own
    rule fetches for them (``ops/pallas/decode.py:paged_keys_fetched``; head
    dim 16 here, so whole pages: a page a grid step); EVA's window and
    summary pages count the rows of its two runs and the pages that hold
    them (``eva_keys_fetched``); every other kind registers the pair and
    leaves it still."""
    from deepspeed_tpu.monitor.metrics import MetricsRegistry
    from deepspeed_tpu.ops.pallas.decode import (eva_keys_fetched,
                                                 paged_keys_fetched)

    model, params = built(name)
    reg = MetricsRegistry().enable()
    serve = deepspeed_tpu.init_serving(
        model, config=dict(CASES[name][2]), params=params, mesh=model.mesh,
        registry=reg)
    prompt, n_out = 21, 10
    serve.submit(np.arange(prompt) % 96, max_new_tokens=n_out)
    serve.run()
    attended = reg.get("ds_serve_attn_keys_attended_total").value
    fetched = reg.get("ds_serve_attn_keys_fetched_total").value
    if name in ("full", "looped", "hybrid", "mixer"):
        p = np.arange(prompt, prompt + n_out - 1)
        page, dh = serve.pool.page, model.config.head_dim
        assert attended == (p + 1).sum()
        assert fetched == paged_keys_fetched(p, page, dh).sum() \
            == ((p // page + 1) * page).sum()
    elif name == "eva":
        cfg = model.config
        W, C, page = cfg.eva_window, cfg.eva_chunk, serve.pool.page
        p = np.arange(prompt, prompt + n_out - 1)
        rows = [p % W + 1, p // W * (W // C)]
        assert attended == sum(rows).sum() > 0
        assert fetched == eva_keys_fetched(p, page, cfg.head_dim, W,
                                           C).sum() \
            == sum(-(-n // page) * page for n in rows).sum()
    else:
        assert attended == fetched == 0
    serve.close()


@pytest.mark.parametrize("pos,want", [
    (0, 64), (63, 64), (64, 128), (255, 256), (256, 320), (300, 320),
    (767, 768), (1000, 1024)])
def test_the_fetch_rule_at_a_head_dim_that_fills_the_lanes(pos, want):
    """Pages of 256 walked inside a grid step: the pages before the last
    whole, the last in pieces of 64 up to ``pos``; under the lane tile a
    whole page whatever ``pos``."""
    from deepspeed_tpu.ops.pallas.decode import FETCH_ROWS, paged_keys_fetched

    assert FETCH_ROWS == 64
    assert paged_keys_fetched(pos, 256, 128) == want
    assert paged_keys_fetched(np.asarray([pos]), 256, 128)[0] == want
    assert paged_keys_fetched(pos, 256, 64) == (pos // 256 + 1) * 256


@pytest.mark.parametrize("pos,window,summary", [
    (0, 64, 0), (255, 256, 0), (256, 320, 0), (2047, 2048, 0),
    (2048, 64, 128), (2048 + 300, 320, 128), (2 * 2048 + 63, 64, 256),
    (3 * 2048 + 1023, 1024, 256 + 128), (7 * 2048 + 2047, 2048, 768 + 128)])
def test_the_fetch_rule_of_a_window_and_its_summaries(pos, window, summary):
    """EVA at the cell's sizes (window 2,048 over pages of 256, 128
    summaries a window): each of a row's two runs of pages is fetched as a
    run of full pages is, whole before its last page and that one in pieces
    of 64 up to the rows that count; under the lane tile, whole pages."""
    from deepspeed_tpu.ops.pallas.decode import eva_keys_fetched

    assert eva_keys_fetched(pos, 256, 128, 2048, 16) == window + summary
    assert eva_keys_fetched(np.asarray([pos]), 256, 128, 2048, 16)[0] \
        == window + summary
    rows = pos % 2048 + 1, pos // 2048 * 128
    assert eva_keys_fetched(pos, 256, 64, 2048, 16) \
        == sum(-(-n // 256) * 256 for n in rows)
    # a window of 512 leaves 32 summaries: one piece holds them
    assert eva_keys_fetched(512, 256, 128, 512, 16) == 64 + 64


@pytest.mark.parametrize("fields,words", [
    (dict(mla_sliding=None), "latent_sliding_attention layers and the group"),
    (dict(mla_sliding=dict(test_dots3_note.SLIDING, kv_rank=0)),
     "missing: ['kv_rank']"),
    (dict(sliding_window=0), "missing: ['sliding_window']"),
    (dict(mla_sliding=dict(test_dots3_note.SLIDING, index_topk=16)),
     "an indexer on a sliding layer"),
    (dict(mla_index_heads=0), "come together"),
    (dict(mla_q_rank=0), "the index queries are made from the query's"),
], ids=["no_sizes", "a_size_of_zero", "no_window", "an_indexer", "half_an_indexer",
        "an_indexer_without_its_bottleneck"])
def test_model_config_refuses_by_name(fields, words):
    with pytest.raises(ValueError) as err:
        ModelConfig(**dict(test_dots3_note.FIELDS, **fields))
    assert words in str(err.value)


def test_the_slot_states_shape_is_the_model_modules(built):
    """``SlotState`` takes its arrays from the model's module: heads of a
    square matrix under ``models/kda_mla.py`` (Kimi's and Solar's byte
    counts as they were), the packed tile of ``models/ssm_moe.py`` that is
    not square; the tail in the cache's dtype, the state float32 always."""
    from deepspeed_tpu.models import kda_mla, ssm_moe

    want = {"state": (kda_mla, 4 * 4 * 16 * 16 * 4 + 4 * 3 * 192 * 4),
            "hybrid": (kda_mla, 3 * 4 * 16 * 16 * 4 + 3 * 3 * 192 * 4),
            "mixer": (ssm_moe, 3 * 4 * 16 * 16 * 4 + 3 * 3 * 128 * 4)}
    for name, (module, nbytes) in want.items():
        model, _ = built(name)
        cfg = model.config
        kind = cache_kind(cfg)
        assert kind.pool_args(jnp.float32) == {"slot_state_bytes": nbytes}
        state, tail = module.state_shapes(cfg, 3)
        pool = PagedKVPool(3, 96, page_tokens=8,
                           **kind.pool_args(jnp.bfloat16))
        cache = kind.init_cache(pool, 3, jnp.bfloat16, False)
        assert cache["state"].shape == state and cache["tail"].shape == tail
        assert cache["state"].dtype == jnp.float32
        assert cache["tail"].dtype == jnp.bfloat16
        assert pool.state_bytes == 3 * kind.pool_args(jnp.bfloat16)[
            "slot_state_bytes"]
    # square under KDA, [heads / 2, state dim, 2 heads' values] here
    assert kda_mla.state_shapes(built("hybrid")[0].config, 3)[0][-2:] \
        == (16, 16)
    assert ssm_moe.state_shapes(built("mixer")[0].config, 3)[0] \
        == (3, 3, 2, 16, 32)
