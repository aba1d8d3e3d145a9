"""Which chunk programs a serve engine is ever asked for
(``ServingEngine.chunk_bucket``), over the six kinds of slot cache at the
tiny sizes of ``test_cache_kind``: the buckets' floor follows the kind
(``chunk_rows``: the kernels' 128-query tile under the three latent kinds, the
recurrence's 64-row sub-chunk under a state beside per-head pages, 8 under
the others), a last chunk of a few real rows in a bucket of 128 is
served the tokens of the unchunked reference, a bucket's first call is its
only compile, and ``ds_serve_prefill_pad_rows_total`` counts what the floor
adds."""

import functools
import os

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.engine import pow2_bucket
from deepspeed_tpu.models import CausalLM, ModelConfig
from deepspeed_tpu.monitor.metrics import MetricsRegistry
from deepspeed_tpu.ops.pallas.flash_attention import _DSA_BLOCK_Q, _LANES

from . import test_axk1, test_dots3_note, test_kimi_linear
from .test_cache_kind import CASES, NAMES, built, serve_of  # noqa: F401

LATENT = {"latent": test_axk1, "state": test_kimi_linear,
          "indexed": test_dots3_note}
# chunks of 256 in a window of 384: buckets 128 and 256, and behind a full
# first chunk the window still leaves a whole tile
WIDE = dict(prefill_chunk=256, max_out_tokens=384)


def powers(lo, hi):
    return {lo << i for i in range(hi.bit_length()) if lo << i <= hi}


# -- (a) the buckets a kind is asked for --------------------------------------
def test_the_floor_is_the_kernels_tile_and_is_written_once():
    from deepspeed_tpu.serving import cache_kind as ck

    assert ck.LatentPages.chunk_rows == _LANES == 128
    assert _DSA_BLOCK_Q == ck.LatentPages.chunk_rows
    assert "chunk_rows" not in vars(ck.LatentPagesAndState)
    assert "chunk_rows" not in vars(ck.IndexedLatentPagesAndRing)
    assert {k.chunk_rows for k in (ck.FullPages, ck.WindowSummaryPages,
                                   ck.TwoBudgets)} == {8}
    from deepspeed_tpu.models import kda_mla
    # a slot state beside per-head pages: the model module's block
    assert "chunk_rows" not in vars(ck.FullPagesAndState)
    assert kda_mla.chunk_rows(None) == kda_mla.SUB == 64


@pytest.mark.parametrize("name,chunk", [
    *((n, None) for n in NAMES),            # the model tests' own chunk
    *((n, c) for n in LATENT for c in (512, 64, 24)),
    ("full", 4), ("latent", 4)])
def test_the_buckets_asked_for_over_every_chunk_length(built, name, chunk):
    """Over ``c = 1 .. prefill_chunk`` at a slot's start: powers of two from
    the kind's floor (never above ``prefill_chunk``, never under the 8 rows
    the engine's smallest program always had) to the first that holds
    ``prefill_chunk``; at the end of the slot's window the cap."""
    kw = {} if chunk is None else dict(prefill_chunk=chunk,
                                       max_out_tokens=max(96, 2 * chunk))
    serve = serve_of(built, name, **kw)
    chunk = serve.prefill_chunk
    floor = max(8, min(128 if name in LATENT else
                       64 if name == "hybrid" else 8, chunk))
    got = {serve.chunk_bucket(c, 0) for c in range(1, chunk + 1)}
    assert got == powers(floor, max(chunk, 8)) and min(got) == floor
    assert floor <= max(chunk, 8)
    for c in range(1, chunk + 1):           # the one rule, from the floor up
        assert serve.chunk_bucket(c, 0) == pow2_bucket(c, lo=floor)
        assert serve.chunk_bucket(c, 0) >= c
    left = 24                               # rows left of the slot's window
    for c in (1, 7, left):
        assert serve.chunk_bucket(c, serve.cache_len - left) == min(
            pow2_bucket(c, lo=floor), left)
    serve.close()


# -- (b), (c), (d): one engine a kind, chunks of 256 ---------------------------
@pytest.fixture(scope="module")
def wide(built):
    """name -> (engine with a private registry on, its registry, the model's
    parameters): a latent kind at ``WIDE`` (its model with room for 384
    positions), another kind at its model tests' sizes."""
    made = {}

    def get(name):
        if name not in made:
            model, params = built(name)
            kw = {}
            if name in LATENT:
                model = CausalLM(ModelConfig(**dict(CASES[name][1],
                                                    max_seq_len=512)),
                                 model.mesh)
                kw = WIDE
            reg = MetricsRegistry().enable()
            made[name] = deepspeed_tpu.init_serving(
                model, config=dict(CASES[name][2], **kw), params=params,
                mesh=model.mesh, registry=reg), reg, params
        return made[name]

    yield get
    for serve, *_ in made.values():
        serve.close()


@functools.lru_cache(maxsize=None)
def _reference(file):
    """``benchmarks/reference/<file>.py``, loaded once: its jitted pieces
    are then compiled once a shape, not once a case."""
    return test_axk1._load("_bucket_ref_" + file, os.path.join(
        test_axk1.REPO, "benchmarks", "reference", file + ".py"))


def reference_tokens(name, params, seq, rows):
    """The argmax of the model's reference forward over the whole sequence
    (``benchmarks/reference``), as its model's parity tests read it; a
    sequence past one block of the reference's queries is padded on the right
    to whole blocks, as the benchmark's driver pads it (causal: no row read
    sees the pad)."""
    t = LATENT[name]
    if len(seq) > 128:
        seq = np.concatenate([seq, np.zeros(-len(seq) % 128, seq.dtype)])
    ref = _reference({"latent": "axk1", "state": "kimi_linear",
                      "indexed": "dots3_note"}[name])
    kw = t.own_choices(ref, params, seq) if name == "indexed" else {}
    return list(t.ref_logits(ref, params, seq, rows, **kw).argmax(-1))


@pytest.mark.parametrize("prompt", [1, 7, 65, 127, 256 + 7],
                         ids=lambda n: f"last_chunk_of_{n % 256}"
                                       + ("_behind_a_full_one" * (n > 256)))
@pytest.mark.parametrize("name", list(LATENT))
def test_a_short_last_chunk_in_the_tile_is_served_the_references_tokens(
        wide, name, prompt):
    """A last chunk of 1, 7, 65 or 127 real rows runs in the bucket of 128,
    up to 127 pad rows behind them (routed like any row, idle in the
    attention, no row of the ring, the state left as of the last real row);
    the 7 behind a full chunk of 256 find the rings wrapped sixteen times.
    Every served token is the argmax of the unchunked reference forward."""
    serve, reg, params = wide(name)
    pads = reg.get("ds_serve_prefill_pad_rows_total")
    toks = reg.get("ds_serve_prefill_tokens_total")
    before = pads.value, toks.value, set(serve._prefill_fns)
    vocab = serve.module.config.vocab_size
    p = np.random.default_rng(prompt).integers(0, vocab, prompt)
    r = serve.submit(p, max_new_tokens=6)
    serve.run()
    serve.pool.check_no_leak()
    assert serve.pool.pages_used == 0
    seq = np.concatenate([p, r.output_tokens])
    assert list(r.output_tokens) == reference_tokens(
        name, params, seq, list(range(prompt - 1, len(seq) - 1)))
    asked = {256, 128} if prompt > 256 else {128}
    assert asked <= set(serve._prefill_fns)
    assert set(serve._prefill_fns) - before[2] <= asked
    # a chunk's bucket less its real rows
    assert toks.value - before[1] == prompt
    assert pads.value - before[0] == 128 - prompt % 256


@pytest.mark.parametrize("name", NAMES)
def test_a_buckets_first_call_is_its_only_compile(wide, name):
    """The benchmark's warm-up (``benchmarks/drivers/serve_open_loop.py``):
    a prompt a power of two from 8 to ``prefill_chunk`` and one past a
    chunk, then each again behind another request's chunk and behind a
    decode block.  The four shortest land in a latent kind's bucket of 128;
    no program, chunk or block, is traced twice (the pool's arrays, the
    carries and the key are placed once, at construction), and the engine's
    counter says one compile a program."""
    serve, reg, _ = wide(name)
    chunk = serve.prefill_chunk
    n_new = serve._K + 2
    lengths = sorted(powers(8, chunk)) + [chunk + 8]
    lengths = [n for n in lengths if n + n_new <= serve.max_out]
    rng = np.random.default_rng(0)
    vocab = serve.module.config.vocab_size
    fresh = lambda n: serve.submit(rng.integers(0, vocab, n),
                                   max_new_tokens=n_new)
    pads = reg.get("ds_serve_prefill_pad_rows_total")
    pad0, want = pads.value, 0
    for n in lengths:
        fresh(n)
        want += sum(serve.chunk_bucket(min(chunk, n - off), off)
                    - min(chunk, n - off) for off in range(0, n, chunk))
    serve.run()
    assert pads.value - pad0 == want
    for n in lengths:
        fresh(lengths[-1]), fresh(n)
        serve.run()
        fresh(n)
        serve.run()
    serve.pool.check_no_leak()
    floor = max(8, min(serve.kind.chunk_rows, chunk))
    buckets = set(serve._prefill_fns)
    assert buckets == powers(floor, chunk)
    assert {b: f._cache_size() for b, f in serve._prefill_fns.items()} == \
        dict.fromkeys(buckets, 1)
    assert serve._block_fn._cache_size() == 1
    assert reg.get("ds_serve_compiles_total").value == (
        len(buckets) + 1 + (serve._cow_copy is not None))
