"""The serve path's own spans, counters and stamps (ISSUE 25): the first
token is stamped where its value reaches the host, on every path; a host
phase's span and its counter are written together and cost nothing the
chip runs; and the benchmark's readers of them decompose the TTFT it times
from outside, and the chip's idle time, without remainder.  CPU, tiny
model: counts and identities, never a speed."""

import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm.mesh import build_mesh, set_global_mesh
from deepspeed_tpu.models import causal_lm
from deepspeed_tpu.monitor.metrics import MetricsRegistry, get_registry
from deepspeed_tpu.monitor.request_trace import get_request_tracer
from deepspeed_tpu.profiling.trace import phase
from deepspeed_tpu.serving.engine import SERVE_PHASES
from tests.unit.hlo_text import program_text

from benchmarks.lib import host_spans, request_spans
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.manifest import Bench
from benchmarks.lib.stats import median
from benchmarks.lib.traffic import Arrival

FETCH_DELAY_S = 0.03


@pytest.fixture(scope="module")
def tiny(devices):
    mesh = build_mesh(fsdp=8, devices=devices)
    set_global_mesh(mesh)
    model = causal_lm("llama-tiny", mesh=mesh, num_layers=2, hidden_size=64,
                      intermediate_size=128, num_heads=4, num_kv_heads=2,
                      vocab_size=256, remat=False)
    return model, model.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))


def _engine(tiny, slots=2, **over):
    model, params = tiny
    engine = deepspeed_tpu.init_serving(
        model, config={"dtype": "float32", "max_out_tokens": 64,
                       "kv_page_tokens": 16, **over},
        num_slots=slots, prefill_chunk=4, decode_block_tokens=3)
    engine.set_params(params)
    return engine


@pytest.fixture(scope="module")
def serve(tiny):
    engine = _engine(tiny)
    yield engine
    engine.close()


def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, int(k), dtype=np.int32)
            for k in rng.integers(3, 12, n)]


# ---------------------------------------------------------------------------
# (a) the first token is stamped where it reaches the host
# ---------------------------------------------------------------------------

class _SlowToken:
    """A prefill program's sampled token whose VALUE takes a while to
    reach the host, as behind a busy chip; still an array to jax."""

    def __init__(self, array):
        self.array = array

    def __int__(self):
        time.sleep(FETCH_DELAY_S)
        return int(self.array)

    def copy_to_host_async(self):
        self.array.copy_to_host_async()


@pytest.mark.parametrize("path", ["stream", "eos", "deferred"])
def test_first_token_is_stamped_after_the_fetch(serve, monkeypatch, path):
    real = serve._prefill_fn

    def slow(cb):
        def program(*args):
            tok, cache, carries = real(cb)(*args)
            return _SlowToken(tok), cache, carries
        return program

    monkeypatch.setattr(serve, "_prefill_fn", slow)
    reg = get_registry()
    reg.enable()
    reg.reset()
    tracer = get_request_tracer()
    tracer.reset()
    tracer.enable()
    kw = {"stream": {"stream": True}, "eos": {"eos_token_id": 255},
          "deferred": {}}[path]
    reqs = [serve.submit(p, max_new_tokens=6, **kw) for p in _prompts(3)]
    seen_at = {}
    while serve.scheduler.has_work:
        serve.step()
        for r in reqs:
            if r.output_tokens and r.request_id not in seen_at:
                seen_at[r.request_id] = time.perf_counter()
    by_id = {r["id"]: r for r in tracer.completed()}
    for r in reqs:
        assert r.done and r.output_tokens
        assert r.t_submit <= r.t_admit
        # the value reached the host a fetch later than its program was
        # enqueued (the last chunk's span ends there; the fetch itself now
        # follows the decode block's enqueue), and no token was visible
        # outside before the stamp
        enqueued = max(t1 for kind, _, t1, _, _ in by_id[r.request_id]["spans"]
                       if kind == "prefill_chunk")
        assert r.t_first_token - enqueued >= FETCH_DELAY_S
        assert r.t_first_token <= seen_at[r.request_id]
        assert r.t_first_token <= r.t_finish
        if path == "deferred":
            # the sync-free path fetches at finish, by design
            assert r.pending_blocks == []
            assert r.t_finish - r.t_first_token < FETCH_DELAY_S
        else:
            assert r.t_finish - r.t_first_token > 0
        # the tracer's prefill -> decode edge is the same instant
        assert by_id[r.request_id]["t_first_token"] == r.t_first_token
    ttft = reg.get("ds_serve_ttft_seconds")
    assert ttft.count == len(reqs)
    assert ttft.sum == pytest.approx(
        sum(r.t_first_token - r.t_submit for r in reqs), rel=1e-9)
    assert ttft.sum >= len(reqs) * FETCH_DELAY_S
    # a pace is recorded where tokens reached the host as they were made:
    # a deferred request got all of its tokens at one instant, its finish
    assert reg.get("ds_serve_tpot_seconds").count == (
        0 if path == "deferred" else len(reqs))


class _LoggedToken(_SlowToken):
    """A sampled token that says when its value is read."""

    def __init__(self, array, log):
        super().__init__(array)
        self.log = log

    def __int__(self):
        self.log.append("first_token_fetch")
        return int(self.array)


def test_no_fetch_between_the_last_chunk_and_the_block(serve, monkeypatch):
    """ISSUE 28: in an iteration that decodes, the host reads nothing from
    the device between a chunk's enqueue and the block's: every fetch of
    the iteration (a first token's, the lag-1 drain's) comes behind the
    block, and the first tokens are visible when that ``step()`` returns."""
    log = []
    real_prefill, real_block = serve._prefill_fn, serve._block
    real_fetch = serve._fetch_block

    def prefill(cb):
        def program(*args):
            log.append("chunk")
            tok, cache, carries = real_prefill(cb)(*args)
            return _LoggedToken(tok, log), cache, carries
        return program

    def block():
        def program(*args):
            log.append("block")
            return real_block()(*args)
        return program

    def fetch_block(idx):
        log.append("block_fetch")
        return real_fetch(idx)

    monkeypatch.setattr(serve, "_prefill_fn", prefill)
    monkeypatch.setattr(serve, "_block", block)
    monkeypatch.setattr(serve, "_fetch_block", fetch_block)
    reg = get_registry()
    reg.enable()
    reg.reset()
    reqs = [serve.submit(p, max_new_tokens=6, stream=True)
            for p in _prompts(5, seed=11)]
    overlapped = 0
    while serve.scheduler.has_work:
        del log[:]
        had = [len(r.output_tokens) for r in reqs]
        serve.step()
        assert not serve._owed
        firsts = log.count("first_token_fetch")
        assert firsts == sum(1 for r, k in zip(reqs, had)
                             if k == 0 and r.output_tokens)
        if "block" in log:
            at = log.index("block")
            assert set(log[:at]) <= {"chunk"}, log
            overlapped += firsts
    assert all(r.done and len(r.output_tokens) == 6 for r in reqs)
    assert overlapped == len(reqs)
    assert reg.get("ds_serve_first_token_overlapped_total").value == \
        len(reqs)


# ---------------------------------------------------------------------------
# (b) lateness + queue + prefill + step tail = the TTFT timed from outside
# ---------------------------------------------------------------------------

def test_ttft_parts_sum_to_the_outside_ttft(serve):
    bench = Bench()
    driver = bench.driver("serve_open_loop")
    rng = np.random.default_rng(3)
    # more requests than slots, some due together: queueing and lateness
    schedule = [Arrival(0.02 * (i // 2), p, int(rng.integers(2, 7)))
                for i, p in enumerate(_prompts(10, seed=3))]
    res = driver.drive(serve, schedule, 0.5, 60.0)
    reg = get_registry()
    reg.enable()             # a run that calls a reader has the registry on
    ctx = {"loop": {"records": res["records"], "schedule": schedule,
                    "late_s": res["late_s"], "until_s": 0.5},
           "trace_window": None, "counters": {"begin": reg.snapshot()}}
    parts = host_spans.ttft_parts(ctx)
    assert len(parts) == len(schedule)
    for p, late_s in zip(parts, res["late_s"]):
        assert min(p["queue"], p["prefill"]) >= 0
        # the loop's clock and submit()'s stamp are microseconds apart on
        # a quiet host; a loaded one may put a thread switch between them
        assert p["late"] > late_s - 1e-3 and p["tail"] > -1e-3
        assert p["late"] + p["queue"] + p["prefill"] + p["tail"] == \
            pytest.approx(p["ttft"], abs=1e-6)
    assert median([p["late"] - l for p, l in zip(parts, res["late_s"])]) \
        == pytest.approx(0.0, abs=1e-4)
    assert max(p["queue"] for p in parts) > 0      # someone waited for a slot
    for name, part in (("ttft_queue_p50_ms", "queue"),
                       ("ttft_prefill_p50_ms", "prefill"),
                       ("ttft_step_tail_p50_ms", "tail")):
        assert bench.reader(name).read(ctx) == pytest.approx(
            median([p[part] for p in parts]) * 1e3)
    # a traced run reads requests due a second before the profiler started
    ctx["trace_window"] = (0.5, 0.6)
    assert host_spans.ttft_parts(ctx) == []
    assert bench.reader("ttft_queue_p50_ms").read(ctx) is None


def test_readers_return_nothing_for_a_program_without_the_stamps():
    from types import SimpleNamespace as NS

    # its stamps look the same; the phases' counters are what it lacks
    old = NS(t_submit=1.0, t_admit=1.1, t_first_token=1.2, preemptions=0)
    counters = {"ds_serve_steps_total": 1, "ds_serve_prefill_chunks_total": 1}
    ctx = {"loop": {"records": [NS(req=old, t_first=0.3)],
                    "schedule": [NS(due_s=0.0)], "late_s": [0.0],
                    "until_s": 1.0},
           "trace_window": None, "counters": {"begin": counters,
                                              "trace_start": counters}}
    bench = Bench()
    for name in ("ttft_queue_p50_ms", "ttft_prefill_p50_ms",
                 "ttft_step_tail_p50_ms", "host_work_share"):
        assert bench.reader(name).read(ctx) is None
    ctx["trace"] = None
    assert bench.reader("idle_fetch_share").read(ctx) is None


def test_host_shares_read_the_phase_counters(serve):
    reg = get_registry()
    reg.enable()
    reg.reset()
    snap = lambda: {k: v for k, v in reg.snapshot().items()
                    if isinstance(v, (int, float))}
    begin = snap()
    t0 = time.perf_counter()
    reqs = [serve.submit(p, max_new_tokens=5, stream=True)
            for p in _prompts(4, seed=5)]
    serve.run()
    wall = time.perf_counter() - t0
    end = snap()
    assert all(r.done for r in reqs)
    ctx = {"counters": {"begin": begin, "trace_start": end},
           "loop": {"until_s": wall}}
    bench = Bench()
    work = bench.reader("host_work_share").read(ctx)
    step_s = end["ds_serve_step_seconds_total"]
    blocked_s = (end["ds_serve_first_token_fetch_seconds_total"]
                 + end["ds_serve_block_fetch_seconds_total"])
    assert work == pytest.approx(100.0 * (step_s - blocked_s) / wall)
    assert 0 < work < 100 and blocked_s > 0
    # every phase was entered and has one counter, its seconds; how often
    # is counted by the code inside (steps, chunks); children fit inside
    # their parents
    for name in SERVE_PHASES:
        assert end[name + "_seconds_total"] > 0, name
        assert name + "_total" not in end
    assert end["ds_serve_steps_total"] > 0
    assert end["ds_serve_prefill_chunks_total"] >= len(reqs)
    parents = sum(end[f"ds_serve_{p}_seconds_total"]
                  for p in ("admit", "prefill", "decode"))
    assert parents <= step_s
    assert end["ds_serve_prefill_dispatch_seconds_total"] <= \
        end["ds_serve_prefill_seconds_total"]
    # every request here decodes, so each first token was fetched inside
    # ds_serve_decode, behind the block's enqueue
    assert end["ds_serve_first_token_overlapped_total"] == len(reqs)
    assert end["ds_serve_decode_dispatch_seconds_total"] + \
        end["ds_serve_first_token_fetch_seconds_total"] <= \
        end["ds_serve_decode_seconds_total"]


# ---------------------------------------------------------------------------
# (b') ... and prefill = chunk_wait + chunks + backlog (ISSUE 38): two more
# stamps on every request, three counters beside them
# ---------------------------------------------------------------------------

def _counting():
    """Registry and request tracer on and empty: the chunk programs a request
    was given are its tracer record's ``prefill_chunk`` spans."""
    reg, tracer = get_registry(), get_request_tracer()
    reg.enable()
    reg.reset()
    tracer.reset()
    tracer.enable()
    return reg, tracer


def _turns_due(serve):
    """The turns the coming iteration will count: the requests that hold a
    slot with prompt left, and those it is about to admit."""
    return len(serve.scheduler.prefilling()) + min(
        serve.scheduler.num_queued, len(serve.scheduler.free_slots()))


def _chunk_spans(tracer, req):
    rec = next(r for r in tracer.completed() if r["id"] == req.request_id)
    return [s for s in rec["spans"] if s[0] == "prefill_chunk"]


def _check_stamps(r):
    assert r.t_admit <= r.t_first_chunk <= r.t_last_chunk <= r.t_first_token
    # differences of neighbouring perf_counter() values are exact, and so is
    # their sum: the three parts ARE the prefill part, to the last bit
    assert ((r.t_first_chunk - r.t_admit) + (r.t_last_chunk - r.t_first_chunk)
            + (r.t_first_token - r.t_last_chunk)
            ) == r.t_first_token - r.t_admit


@pytest.mark.parametrize("path", ["stream", "eos", "deferred"])
def test_chunk_stamps_partition_the_time_in_the_slot(serve, path):
    kw = {"stream": {"stream": True}, "eos": {"eos_token_id": 255},
          "deferred": {}}[path]
    reg, tracer = _counting()
    prompts = _prompts(6, seed=21)
    reqs = [serve.submit(p, max_new_tokens=5, **kw) for p in prompts]
    serve.run()
    for r, p in zip(reqs, prompts):
        assert r.done and not r.preemptions
        _check_stamps(r)
        assert len(_chunk_spans(tracer, r)) == \
            -(-len(p) // serve.prefill_chunk)
    # two slots, two places an iteration: every turn in PREFILLING got its
    # chunk, and a request that prefilled alone the other place too
    assert reg.get("ds_serve_prefill_turns_missed_total").value == 0
    assert reg.get("ds_serve_prefill_turns_total").value == \
        reg.get("ds_serve_prefill_chunks_total").value \
        - reg.get("ds_serve_prefill_chunks_extra_total").value


def test_stamps_are_those_of_the_last_admission(tiny):
    # five pages for two requests of three: the younger one is preempted
    # while it decodes, and comes back through a re-prefill of its prompt
    # and of what it had produced
    serve = _engine(tiny, kv_pool_tokens=80)
    try:
        rng = np.random.default_rng(4)
        prompts = [rng.integers(0, 256, n, dtype=np.int32) for n in (8, 9)]
        reqs = [serve.submit(p, max_new_tokens=40, stream=True)
                for p in prompts]
        admits = {r.request_id: [] for r in reqs}
        while serve.scheduler.has_work:
            serve.step()
            for r in reqs:
                if r.t_admit and r.t_admit not in admits[r.request_id]:
                    admits[r.request_id].append(r.t_admit)
        victims = [r for r in reqs if r.preemptions]
        assert victims and all(r.done for r in reqs)
        for r in victims:
            assert len(admits[r.request_id]) == r.preemptions + 1
            assert r.t_admit == admits[r.request_id][-1]
            # not re-stamped on resume: the first token is the first run's
            assert r.t_first_token < r.t_admit
            # both chunk stamps were taken again, by the re-prefill
            assert r.t_admit <= r.t_first_chunk <= r.t_last_chunk \
                <= r.t_finish
        for r in reqs:
            if not r.preemptions:
                _check_stamps(r)
        # a reader of the stamps leaves the preempted out, as ttft_parts does
    finally:
        serve.close()


def test_turns_missed_and_first_tokens_are_counted(serve, monkeypatch):
    monkeypatch.setattr(serve, "max_prefill_chunks", 1)
    reg, tracer = _counting()
    # two slots, one chunk an iteration: the younger of two prefilling
    # requests is passed over while the older has chunks left
    reqs = [serve.submit(p, max_new_tokens=4, stream=True)
            for p in _prompts(6, seed=8)]
    turns = 0
    while serve.scheduler.has_work:
        turns += _turns_due(serve)
        serve.step()
    assert all(r.done for r in reqs)
    for r in reqs:
        _check_stamps(r)
    chunks = sum(len(_chunk_spans(tracer, r)) for r in reqs)
    assert chunks == sum(-(-r.prompt_len // serve.prefill_chunk)
                         for r in reqs)
    missed = reg.get("ds_serve_prefill_turns_missed_total").value
    assert missed > 0
    assert reg.get("ds_serve_prefill_turns_total").value == turns
    assert missed + chunks == turns
    assert reg.get("ds_serve_prefill_chunks_total").value == chunks
    assert reg.get("ds_serve_prefill_chunks_extra_total").value == 0
    assert reg.get("ds_serve_first_tokens_total").value == len(reqs)
    assert reg.get("ds_serve_first_token_overlapped_total").value <= \
        len(reqs)
    # the request tracer's spans name the launch each one is the ENQUEUE of:
    # a number a chunk, a number a block, none twice over the requests'
    # chunks, a block's shared by its rows
    by_id = {r["id"]: r for r in tracer.completed()}
    chunk_seqs, block_seqs = [], set()
    for r in reqs:
        for kind, _, _, _, seq in by_id[r.request_id]["spans"]:
            if kind == "prefill_chunk":
                chunk_seqs.append(seq)
            elif kind == "decode_block":
                block_seqs.add(seq)
            else:
                assert seq is None
    assert len(set(chunk_seqs)) == len(chunk_seqs) == chunks
    assert not block_seqs & set(chunk_seqs)
    assert max(block_seqs | set(chunk_seqs)) == serve._launch_seq


# ---------------------------------------------------------------------------
# (b'') ... and an iteration's max_prefill_chunks are PLACES (ISSUE 45): one a
# prefilling request by admission, the rest round by round over those with
# prompt left
# ---------------------------------------------------------------------------

def _schedule(serve, reqs, reg):
    """Serve to empty; a row an iteration in which a chunk program ran: how
    many each of ``reqs`` was given.  No iteration runs more of them than it
    has places."""
    rows, per = [], serve.prefill_chunk
    chunks = reg.get("ds_serve_prefill_chunks_total")
    while serve.scheduler.has_work:
        at, n = [r.prefill_pos for r in reqs], chunks.value
        serve.step()
        row = tuple(-(-(r.prefill_pos - a) // per) for r, a in zip(reqs, at))
        assert sum(row) == chunks.value - n <= serve.max_prefill_chunks
        if any(row):
            rows.append(row)
    assert all(r.done and not r.preemptions for r in reqs)
    return rows


def _counts(reg):
    return {k: reg.get(f"ds_serve_prefill_{k}_total").value
            for k in ("turns", "turns_missed", "chunks", "chunks_extra")}


def _prompt(chunks, seed, per=4):
    """A prompt of ``chunks`` chunks, the last one ragged; a seed of its own
    wherever an engine is shared, or the prefix cache prefills it."""
    return np.random.default_rng(seed).integers(
        0, 256, per * chunks - 1, dtype=np.int32)


@pytest.mark.parametrize("places,n", [(1, 3), (2, 1), (2, 5), (2, 6),
                                      (4, 3), (4, 6), (4, 9)])
def test_a_lone_prompt_takes_every_place(serve, monkeypatch, places, n):
    monkeypatch.setattr(serve, "max_prefill_chunks", places)
    reg, _ = _counting()
    req = serve.submit(_prompt(n, seed=100 * places + n), max_new_tokens=3,
                       stream=True)
    rows = _schedule(serve, [req], reg)
    turns = -(-n // places)
    assert rows == [(places,)] * (n // places) + [(n % places,)] * (
        turns - n // places)
    assert _counts(reg) == {"turns": turns, "turns_missed": 0, "chunks": n,
                            "chunks_extra": n - turns}
    _check_stamps(req)


@pytest.mark.parametrize("places,rows", [
    (2, [(1, 1)] * 5),                              # a place each: none left
    (3, [(2, 1), (2, 1), (1, 2), (0, 1)]),          # the odd one to the older
    (4, [(2, 2), (2, 2), (1, 1)])])
def test_leftover_places_go_round_by_age(serve, monkeypatch, places, rows):
    monkeypatch.setattr(serve, "max_prefill_chunks", places)
    reg, _ = _counting()
    reqs = [serve.submit(_prompt(5, seed=10 * places + s), max_new_tokens=3,
                         stream=True) for s in (1, 2)]
    assert _schedule(serve, reqs, reg) == rows
    turns = sum(map(bool, np.ravel(rows)))
    assert _counts(reg) == {"turns": turns, "turns_missed": 0, "chunks": 10,
                            "chunks_extra": 10 - turns}


def test_a_request_beyond_the_places_is_passed_over(tiny):
    serve = _engine(tiny, slots=3)        # max_prefill_chunks 2, the default
    try:
        reg, _ = _counting()
        reqs = [serve.submit(_prompt(2, seed=s), max_new_tokens=3,
                             stream=True) for s in (1, 2, 3)]
        # round 1 spends both places on the two oldest; the third waits for
        # them as it always did, and then prefills alone
        assert _schedule(serve, reqs, reg) == [(1, 1, 0), (1, 1, 0),
                                               (0, 0, 2)]
        assert _counts(reg) == {"turns": 7, "turns_missed": 2, "chunks": 6,
                                "chunks_extra": 1}
        assert reqs[2].t_first_chunk > reqs[1].t_last_chunk
    finally:
        serve.close()


def test_one_place_is_the_older_schedule(serve, monkeypatch):
    monkeypatch.setattr(serve, "max_prefill_chunks", 1)
    reg, _ = _counting()
    reqs = [serve.submit(_prompt(n, seed=50 + n), max_new_tokens=3,
                         stream=True) for n in (3, 2, 4, 1)]
    # one chunk an iteration, the oldest prefilling request's; two slots, so
    # the third is admitted when the first leaves
    for row in _schedule(serve, reqs, reg):
        assert sum(row) == 1
        assert all(r.prefill_pos == r.prompt_len
                   for r in reqs[:row.index(1)])
    assert _counts(reg)["chunks_extra"] == 0


@pytest.mark.parametrize("places", [2, 3, 4])
def test_turns_are_chunks_less_extra_plus_missed(tiny, places):
    serve = _engine(tiny, slots=3)
    serve.max_prefill_chunks = places
    try:
        reg, tracer = _counting()
        reqs = [serve.submit(_prompt(n, seed=n), max_new_tokens=4,
                             stream=True) for n in (5, 1, 3, 7, 2, 4, 6)]
        turns = 0
        while serve.scheduler.has_work:
            turns += _turns_due(serve)
            serve.step()
        got = _counts(reg)
        assert got["turns"] == turns
        assert got["chunks"] == sum(len(_chunk_spans(tracer, r))
                                    for r in reqs) == 28
        assert got["turns_missed"] + got["chunks"] - got["chunks_extra"] \
            == turns
        assert got["chunks_extra"] > 0
        assert (got["turns_missed"] > 0) == (places == 2)
        for r in reqs:
            _check_stamps(r)
    finally:
        serve.close()


@pytest.mark.parametrize("places,refused", [
    # its fifth chunk is the one that needs a page: the first try of an
    # iteration under two places, a later round's under three
    (2, {"turns": 1, "turns_missed": 1, "chunks": 0, "chunks_extra": 0}),
    (3, {"turns": 1, "turns_missed": 0, "chunks": 1, "chunks_extra": 0})])
def test_a_request_refused_pages_gets_no_later_round(tiny, places, refused):
    # five pages: the older request decodes into four of them, the younger
    # one's prompt needs two, so it preempts ITSELF until the older is done
    serve = _engine(tiny, kv_pool_tokens=80)
    serve.max_prefill_chunks = places
    try:
        reg, _ = _counting()
        old = serve.submit(_prompt(2, seed=7), max_new_tokens=56, stream=True)
        while serve.pool.pages_free > 1:
            serve.step()
        young = serve.submit(_prompt(5, seed=8), max_new_tokens=3,
                             stream=True)
        turns, seen = reg.get("ds_serve_prefill_turns_total").value, False
        while serve.scheduler.has_work:
            turns += _turns_due(serve)
            at, was = _counts(reg), young.preemptions
            serve.step()
            if young.preemptions > was and not seen:
                seen = True
                assert {k: v - at[k] for k, v in _counts(reg).items()} \
                    == refused
                assert young.prefill_pos == 0 and young.slot < 0
        assert seen and old.done and young.done and not old.preemptions
        got = _counts(reg)
        assert got["turns"] == turns
        assert got["turns_missed"] + got["chunks"] - got["chunks_extra"] \
            == turns
    finally:
        serve.close()


def test_readers_of_the_stamps_and_counters(serve):
    bench = Bench()
    driver = bench.driver("serve_open_loop")
    reg = get_registry()
    reg.enable()
    reg.reset()
    snap = lambda: {k: v for k, v in reg.snapshot().items()
                    if isinstance(v, (int, float))}
    begin = snap()
    rng = np.random.default_rng(13)
    schedule = [Arrival(0.02 * (i // 2), p, int(rng.integers(2, 7)))
                for i, p in enumerate(_prompts(10, seed=13))]
    res = driver.drive(serve, schedule, 0.5, 60.0)
    end = snap()
    ctx = {"loop": {"records": res["records"], "schedule": schedule,
                    "late_s": res["late_s"], "until_s": 0.5},
           "trace_window": None,
           "counters": {"begin": begin, "trace_start": end}}
    parts = request_spans.stamp_parts(ctx)
    whole = host_spans.ttft_parts(ctx)
    assert len(parts) == len(whole) == len(schedule)
    for p, w in zip(parts, whole):       # the same requests, in one order
        assert p["prefill"] == w["prefill"]
        assert p["chunk_wait"] + p["chunks"] + p["backlog"] == p["prefill"]
        assert min(p["chunk_wait"], p["chunks"], p["backlog"]) >= 0
    for name, part in (("ttft_chunk_wait_p50_ms", "chunk_wait"),
                       ("ttft_chunks_p50_ms", "chunks"),
                       ("ttft_backlog_p50_ms", "backlog")):
        assert bench.reader(name).read(ctx) == pytest.approx(
            median([p[part] for p in parts]) * 1e3)
    d = lambda k: end[k] - begin[k]
    assert bench.reader("prefill_turns_missed_share").read(ctx) == \
        pytest.approx(100.0 * d("ds_serve_prefill_turns_missed_total")
                      / d("ds_serve_prefill_turns_total"))
    assert d("ds_serve_first_tokens_total") == len(schedule)
    assert bench.reader("first_token_overlapped_share").read(ctx) == \
        pytest.approx(100.0 * d("ds_serve_first_token_overlapped_total")
                      / len(schedule))
    # no device trace (ctx["trace"] is what run.py reduced one to): the
    # joined metrics are left out
    ctx["trace"] = None
    for name in ("ttft_backlog_decode_p50_ms", "ttft_backlog_chunks_p50_ms",
                 "ttft_own_chunk_p50_ms"):
        assert bench.reader(name).read(ctx) is None
    # a traced run reads requests due a second before the profiler started
    ctx["trace_window"] = (0.5, 0.6)
    assert request_spans.stamp_parts(ctx) == []
    assert bench.reader("ttft_backlog_p50_ms").read(ctx) is None


def test_new_readers_return_nothing_for_the_parent_program():
    from types import SimpleNamespace as NS

    # PR 37's program: the phases' counters and the overlapped counter, no
    # chunk stamps, no turn counters, no count of first tokens
    old = NS(t_submit=1.0, t_admit=1.1, t_first_token=1.2, preemptions=0)
    counters = {"ds_serve_first_token_fetch_seconds_total": 0.1,
                "ds_serve_first_token_overlapped_total": 3}
    ctx = {"loop": {"records": [NS(req=old, t_first=0.3)],
                    "schedule": [NS(due_s=0.0)], "late_s": [0.0],
                    "until_s": 1.0},
           "trace_window": None, "trace": None,
           "counters": {"begin": counters, "trace_start": counters}}
    bench = Bench()
    assert bench.reader("ttft_prefill_p50_ms").read(ctx) is not None
    for name in ("ttft_chunk_wait_p50_ms", "ttft_chunks_p50_ms",
                 "ttft_backlog_p50_ms", "prefill_turns_missed_share",
                 "first_token_overlapped_share",
                 "ttft_backlog_decode_p50_ms", "ttft_backlog_chunks_p50_ms",
                 "ttft_own_chunk_p50_ms"):
        assert bench.reader(name).read(ctx) is None, name


def test_dispatch_ranges_carry_the_launch_number(serve, tmp_path):
    """In a profiler session the two dispatch ranges keep their bare names
    (what ``idle_by_span`` and the driver's ``HOST_SCOPES`` match) and say
    in their stats what they enqueued; ``seq`` rises by one from each
    dispatch, of either kind, to the next."""
    first = serve._launch_seq
    jax.profiler.start_trace(str(tmp_path))
    try:
        reqs = [serve.submit(p, max_new_tokens=5, stream=True)
                for p in _prompts(4, seed=17)]
        serve.run()
    finally:
        jax.profiler.stop_trace()
    trace = tr.load_xplane(tr.find_xplane(str(tmp_path)),
                           planes=(r"^/host:CPU$",), all_stats=True)
    chunks = tr.host_events(trace, "ds_serve_prefill_dispatch")
    blocks = tr.host_events(trace, "ds_serve_decode_dispatch")
    assert len(chunks) == sum(-(-r.prompt_len // serve.prefill_chunk)
                              for r in reqs) and blocks
    both = sorted(chunks + blocks, key=lambda e: e.start)
    assert [e.stats["seq"] for e in both] == list(
        range(first + 1, first + 1 + len(both)))
    assert serve._launch_seq == first + len(both)
    ids = {r.request_id: r for r in reqs}
    # the stats are the three the benchmark's join reads, and no other
    for e in chunks:
        assert set(e.stats) == {"seq", "request_id", "last"}
    for rid, r in ids.items():
        mine = [e for e in chunks if e.stats["request_id"] == rid]
        assert len(mine) == -(-r.prompt_len // serve.prefill_chunk)
        assert [e.stats["last"] for e in mine] == [0] * (len(mine) - 1) + [1]
    for e in blocks:
        assert set(e.stats) == {"seq"}
    # the other ranges say nothing of a launch
    assert all("seq" not in e.stats
               for n in SERVE_PHASES if not n.endswith("_dispatch")
               for e in tr.host_events(trace, n))
    assert tr.host_events(trace, "ds_serve_step")
    # the benchmark's join reads them in launch order; a CPU trace has no
    # chip to join them to
    assert [e.stats["seq"] for e in request_spans.dispatches(trace)] == \
        [e.stats["seq"] for e in both]
    with pytest.raises(tr.NoDeviceTrace):
        request_spans.join(trace)


# ---------------------------------------------------------------------------
# (c) phase(): one span, one counter, nothing the chip runs
# ---------------------------------------------------------------------------

def test_phase_counter_equals_its_spans(tmp_path):
    reg = MetricsRegistry()
    with phase("ds_test_phase", registry=reg):           # registry off
        time.sleep(0.001)
    assert reg.names() == []
    reg.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(8):
            with phase("ds_test_phase", registry=reg):
                time.sleep(0.005)
            with phase("ds_test_step", registry=reg, step_num=i):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    trace = tr.load_xplane(tr.find_xplane(str(tmp_path)))
    for name, least in (("ds_test_phase", 0.04), ("ds_test_step", 0.016)):
        spans = tr.host_events(trace, name)
        assert len(spans) == 8
        assert reg.get(name + "_total") is None         # seconds only
        seconds = reg.get(name + "_seconds_total").value
        assert seconds >= least
        assert seconds == pytest.approx(sum(e.dur for e in spans) / 1e9,
                                        rel=0.05)


def _programs(serve):
    serve._block_fn, serve._prefill_fns = None, {}       # trace them anew
    serve.pool.ensure(0, 8)
    try:
        block = serve._block().lower(
            serve._loop_params(), serve._cache, serve._last_dev,
            serve._pos_dev, serve._act_dev, jnp.asarray(serve._limit),
            jnp.asarray(serve._eos), serve._rng,
            jnp.asarray(serve.pool.page_table))
        prefill = serve._prefill_fn(8).lower(
            serve.engine._params, serve._cache,
            (serve._last_dev, serve._pos_dev, serve._act_dev),
            jnp.asarray(serve.pool.page_table[0]), jnp.zeros((1, 8), jnp.int32),
            jnp.asarray([0, 0, 7, 1, -1], jnp.int32), serve._rng)
    finally:
        serve.pool.release(0)
    return program_text(block.compile()), program_text(prefill.compile())


def test_programs_are_the_same_with_tracing_on_and_off(serve, tmp_path):
    reg, tracer = get_registry(), get_request_tracer()
    reg.disable()
    tracer.disable()
    off = _programs(serve)
    reg.enable()
    tracer.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        on = _programs(serve)
    finally:
        jax.profiler.stop_trace()
    assert all("ENTRY" in t for t in off) and on == off


# ---------------------------------------------------------------------------
# (d) the chip's idle time, range by range, on traces recorded on a v5e:
# 150 ms from the middle of the traced window of mistral-7b-L8.serve-chat,
# cut by `python -m benchmarks.lib.host_spans <trace dir> --cut`.  One from
# before ISSUE 28 (my chip run, PR 25: the first token fetched and the slot
# woken inside ds_serve_prefill), one from after it (my chip run, PR 28:
# the fetch inside ds_serve_decode, behind the block's enqueue; no wake)
# ---------------------------------------------------------------------------

FIXTURES = os.path.join(os.path.dirname(tr.__file__), os.pardir, "tests",
                        "fixtures")
SPANS_FIXTURES = {
    "pr25": (os.path.join(FIXTURES, "v5e_serve_spans_150ms.json.gz"),
             {"ds_serve_prefill": ("ds_serve_prefill_dispatch",
                                   "ds_serve_first_token_fetch",
                                   "ds_serve_wake"),
              "ds_serve_decode": ("ds_serve_decode_dispatch",)}),
    "pr28": (os.path.join(os.path.dirname(__file__), "fixtures",
                          "v5e_serve_spans_pr28_150ms.json.gz"),
             {"ds_serve_prefill": ("ds_serve_prefill_dispatch",),
              "ds_serve_decode": ("ds_serve_decode_dispatch",
                                  "ds_serve_first_token_fetch")}),
}


@pytest.mark.parametrize("recorded", sorted(SPANS_FIXTURES))
def test_idle_shares_partition_the_device_idle_share(recorded):
    path, under = SPANS_FIXTURES[recorded]
    trace = tr.load_events(path)
    idle = host_spans.idle_by_span(trace)
    summary = tr.summarize(trace, host_scopes=(
        "ds_serve_admit", "ds_serve_prefill", "ds_serve_decode"))
    share = lambda s: 100.0 * s / idle["window_s"]
    device_idle_share = 100.0 * (
        1.0 - summary["busy_s_chip0"] / summary["window_s"])
    assert share(idle["fetch_s"]) + share(idle["host_work_s"]) + \
        share(idle["outside_s"]) == pytest.approx(device_idle_share, abs=0.1)
    assert idle["idle_s"] > 0 and idle["host_work_s"] > 0
    assert sum(idle["by_span"].values()) == pytest.approx(idle["idle_s"])
    # the children refine the three parents the ledger's idle_gaps has:
    # what summarize() puts under a parent is that parent's self time plus
    # its children's
    spans = idle["by_span"]
    for parent, children in under.items():
        assert spans[parent] <= summary["idle_gaps"].get(parent, 0.0) + 1e-9
        assert sum(spans.get(c, 0.0) for c in children) <= \
            summary["idle_gaps"].get(parent, 0.0) + 1e-9
    assert "ds_serve_step" in idle["spans"]
    fetches = tr.host_events(trace, "ds_serve_first_token_fetch")
    assert fetches
    if recorded == "pr28":
        # each first token is read inside a decode range, after that
        # range's dispatch: the block is queued behind the chunk
        assert "ds_serve_wake" not in idle["spans"]
        end = lambda e: e.start + e.dur
        behind = 0
        for f in fetches:
            (dec,) = [d for d in tr.host_events(trace, "ds_serve_decode")
                      if d.start <= f.start and end(f) <= end(d)]
            inside = [d for d in tr.host_events(
                trace, "ds_serve_decode_dispatch")
                if dec.start <= d.start and end(d) <= end(dec)]
            # (the cut may have taken a range's beginning, dispatch and all)
            assert all(end(d) <= f.start for d in inside)
            behind += bool(inside)
        assert behind
        assert device_idle_share < 5.0
    # a trace of a program without the ranges gives nothing, not zeros
    for lines in trace.values():
        for line, evs in lines.items():
            lines[line] = [e for e in evs if e.name != host_spans.STEP]
    assert host_spans.idle_by_span(trace) is None
