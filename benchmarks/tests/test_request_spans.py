"""Where a first token's time in its slot goes (``lib/request_spans.py``):
the stamps' three parts, the two counter shares, and the join of the
dispatch ranges' launch numbers to the chip's programs, on a hand-made
trace whose every number can be checked by eye and on two seconds of trace
recorded on a v5e (``fixtures/``)."""

import os
from types import SimpleNamespace as NS

import pytest

from benchmarks.lib import host_spans
from benchmarks.lib import request_spans as rs
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.manifest import Bench
from benchmarks.lib.stats import median
from benchmarks.lib.trace_reduce import Ev

from conftest import TESTS_DIR

CHUNK_RANGE, BLOCK_RANGE = "ds_serve_prefill_dispatch", \
    "ds_serve_decode_dispatch"
FETCH_RANGE = "ds_serve_first_token_fetch"
# the readers of the join: the part each takes a median of, over the
# requests behind a block or over all
JOINED = {"ttft_backlog_decode_p50_ms": ("decode", True),
          "ttft_backlog_chunks_p50_ms": ("chunks", True),
          "ttft_own_chunk_p50_ms": ("program", False),
          "last_chunk_behind_block_share": (None, False)}


# ---------------------------------------------------------------------------
# the stamps and the counters
# ---------------------------------------------------------------------------

def _ctx(reqs, counters=None):
    counters = counters or {"ds_serve_first_token_fetch_seconds_total": 0.0}
    return {"loop": {"records": [NS(req=r, t_first=r.t_first_token - 100.0
                                    + 0.001) for r in reqs],
                     "schedule": [NS(due_s=r.t_submit - 100.0) for r in reqs],
                     "late_s": [0.0] * len(reqs), "until_s": 10.0},
            "trace_window": None, "trace": None,
            "counters": {"begin": counters, "trace_start": counters}}


def _req(t, wait, chunks, backlog, **over):
    r = NS(t_submit=t, t_admit=t + 0.001, preemptions=0)
    r.t_first_chunk = r.t_admit + wait
    r.t_last_chunk = r.t_first_chunk + chunks
    r.t_first_token = r.t_last_chunk + backlog
    r.__dict__.update(over)
    return r


def test_stamp_readers_take_the_requests_ttft_parts_takes():
    reqs = [_req(100.0 + 0.1 * i, 0.001 * i, 0.002 * i, 0.05 + 0.001 * i)
            for i in range(7)]
    reqs[2].preemptions = 1              # its stamps are the last admission's
    ctx = _ctx(reqs)
    parts = rs.stamp_parts(ctx)
    assert len(parts) == 6
    for p in parts:
        assert p["chunk_wait"] + p["chunks"] + p["backlog"] == \
            pytest.approx(p["prefill"], abs=1e-12)
    kept = [i for i in range(7) if i != 2]
    bench = Bench()
    for name, want in (
            ("ttft_chunk_wait_p50_ms", median([1.0 * i for i in kept])),
            ("ttft_chunks_p50_ms", median([2.0 * i for i in kept])),
            ("ttft_backlog_p50_ms", median([50.0 + i for i in kept]))):
        assert bench.reader(name).read(ctx) == pytest.approx(want, abs=1e-6)
    assert bench.reader("ttft_prefill_p50_ms").read(ctx) == pytest.approx(
        median([p["prefill"] for p in parts]) * 1e3)
    # the parent's requests have no chunk stamps: nothing, not zeros
    for r in reqs:
        del r.t_first_chunk, r.t_last_chunk
    assert rs.stamp_parts(ctx) is None
    assert bench.reader("ttft_backlog_p50_ms").read(ctx) is None
    assert bench.reader("ttft_prefill_p50_ms").read(ctx) is not None


def test_counter_readers_take_growth_to_the_profilers_start():
    bench = Bench()
    begin = {"ds_serve_prefill_turns_total": 100,
             "ds_serve_prefill_turns_missed_total": 10,
             "ds_serve_first_tokens_total": 50,
             "ds_serve_first_token_overlapped_total": 40}
    at = {"ds_serve_prefill_turns_total": 300,
          "ds_serve_prefill_turns_missed_total": 16,
          "ds_serve_first_tokens_total": 150,
          "ds_serve_first_token_overlapped_total": 135}
    ctx = {"counters": {"begin": begin, "trace_start": at,
                        "end": {k: 10 * v for k, v in at.items()}}}
    assert bench.reader("prefill_turns_missed_share").read(ctx) == \
        pytest.approx(3.0)
    assert bench.reader("first_token_overlapped_share").read(ctx) == \
        pytest.approx(95.0)
    # PR 37's program counts the overlapped first tokens and not all of them
    for gone in ("ds_serve_prefill_turns_total",
                 "ds_serve_first_tokens_total"):
        del begin[gone], at[gone]
    assert bench.reader("prefill_turns_missed_share").read(ctx) is None
    assert bench.reader("first_token_overlapped_share").read(ctx) is None


# ---------------------------------------------------------------------------
# the join, by eye
# ---------------------------------------------------------------------------

T = 10_000        # ns a unit of the hand-made trace: a block is 20 ms


def chunk(seq, rid, last, start, dur=100):
    return Ev(CHUNK_RANGE, start * T, dur * T,
              {"seq": seq, "request_id": rid, "last": last})


def block(seq, start, dur=100):
    return Ev(BLOCK_RANGE, start * T, dur * T, {"seq": seq})


def fetch(start, end):
    return Ev(FETCH_RANGE, start * T, (end - start) * T, {})


def prog(name, start, dur):
    return Ev(name, start * T, dur * T, {})


def hand_made():
    """One chip, the window 1,000-9,000 (units of 10 us).  The host
    enqueues (launch number: range), the chip runs, and the host's fetch of
    a last chunk's token returns 10 after the program's end:

        5: chunk, request 1, last    1100-1200   jit_prefill   2020-2300
        6: block                     1300-1400   jit_body      2300-4300
        7: chunk, request 2          3000-3100   jit_prefill_8 4300-4500
        8: chunk, request 3, last    3200-3300   jit_prefill   4500-4800
        9: block                     3400-3500   jit_body      4800-6800
       10: chunk, request 2, last    6000-6100   jit_prefill   6900-7200
       11: block                     6200-6300   (not run in the trace)

    before them a block enqueued before the profiler started (0-2000) and a
    page copy, which has no launch number (2000-2010)."""
    host = [Ev(tr.WINDOW_MARK, 1000 * T, 8000 * T, {}),
            Ev("ds_serve_step", 1050 * T, 500 * T, {"step_num": 3}),
            chunk(5, 1, 1, 1100), block(6, 1300), fetch(1400, 2310),
            chunk(7, 2, 0, 3000), chunk(8, 3, 1, 3200), block(9, 3400),
            fetch(3500, 4810), chunk(10, 2, 1, 6000), block(11, 6200),
            fetch(6300, 7210)]
    chip = [prog("jit_body(1)", 0, 2000), prog("jit_cow(9)", 2000, 10),
            prog("jit_prefill(2)", 2020, 280), prog("jit_body(1)", 2300, 2000),
            prog("jit_prefill_8(3)", 4300, 200),
            prog("jit_prefill(2)", 4500, 300), prog("jit_body(1)", 4800, 2000),
            prog("jit_prefill(2)", 6900, 300)]
    return {"/host:CPU": {"python3": host},
            "/device:TPU:0": {tr.MODULES_LINE: chip}}


def test_join_by_eye():
    trace = hand_made()
    joined = rs.join(trace)
    assert [(r.stats["seq"], p and p.start // T) for r, p in joined] == [
        (5, 2020), (6, 2300), (7, 4300), (8, 4500), (9, 4800), (10, 6900),
        (11, None)]
    split = rs.backlog_split(trace)
    ns = T * 1e-9
    want = {1: dict(interval=1100, decode=800, chunks=0, own=280, idle=20),
            3: dict(interval=1500, decode=1000, chunks=200, own=300, idle=0),
            2: dict(interval=1100, decode=700, chunks=0, own=300, idle=100)}
    assert [s["request_id"] for s in split] == [1, 3, 2]
    for s in split:
        for k, v in want[s["request_id"]].items():
            assert s[k] == pytest.approx(v * ns), (s["request_id"], k)
        assert sum(s[p] for p in rs.PARTS) == pytest.approx(s["interval"])
        assert s["program_starts_after_enqueue"] > 0
        assert s["program"] == s["own"]      # each waited: none was clipped
    # a median over at least `min_requests` of them, else nothing
    assert rs.backlog_part_p50_ms(split, "decode", min_requests=3) == \
        pytest.approx(800e-6 * T)
    assert rs.backlog_part_p50_ms(split, "decode", min_requests=4) is None
    assert rs.backlog_part_p50_ms(split, "decode") is None       # 8
    assert rs.backlog_part_p50_ms(None, "decode", min_requests=0) is None
    assert rs.behind_a_block(split) == split
    assert rs.behind_block_share(split, min_requests=3) == 100.0
    assert rs.behind_block_share(split) is None


def test_a_chunk_enqueued_on_an_idle_chip_is_the_other_kind():
    # request 2's last chunk again, the block before it over by 5900 and
    # the program started at 6050, inside its enqueue (6000-6100)
    def on_idle(evs):
        return [e._replace(dur=1100 * T) if e.start == 4800 * T
                else e._replace(start=6050 * T) if e.start == 6900 * T else e
                for e in evs]
    trace = _edit(_edit(hand_made(), "/device:TPU:0", on_idle), "/host:CPU",
                  lambda evs: [fetch(6300, 6360) if e.name == FETCH_RANGE
                               and e.start == 6300 * T else e for e in evs])
    split = rs.backlog_split(trace)
    two = split[-1]
    assert two["request_id"] == 2 and two["decode"] == 0
    # the interval runs from the enqueue's end: 250 of the program's 300
    assert two["own"] == two["interval"] == pytest.approx(250e-9 * T)
    assert two["program"] == pytest.approx(300e-9 * T)
    assert [s["request_id"] for s in rs.behind_a_block(split)] == [1, 3]
    assert rs.behind_block_share(split, min_requests=3) == \
        pytest.approx(200 / 3)
    # the medians of the wait are over the kind that waited
    assert rs.backlog_part_p50_ms(rs.behind_a_block(split), "decode",
                                  min_requests=2) == pytest.approx(9e-4 * T)


def _edit(trace, plane, fn):
    (line, evs), = trace[plane].items()
    trace[plane][line] = fn(evs)
    return trace


def test_join_gives_nothing_where_the_count_does_not_close():
    no_seq = _edit(hand_made(), "/host:CPU", lambda evs: [
        e._replace(stats={}) for e in evs])
    assert rs.dispatches(no_seq) is None and rs.backlog_split(no_seq) is None
    # a launch number is missing (a range the trace dropped)
    gap = _edit(hand_made(), "/host:CPU", lambda evs: [
        e for e in evs if e.stats.get("seq") != 8])
    assert rs.join(gap) is None
    # more programs than ranges after any number of leading ones
    extra = _edit(hand_made(), "/device:TPU:0", lambda evs: evs + [
        prog("jit_body(1)", 7200, 1000), prog("jit_body(1)", 8200, 500)])
    assert rs.join(extra) is None
    # the families disagree whatever is dropped
    wrong = _edit(hand_made(), "/device:TPU:0", lambda evs: [
        e._replace(name="jit_body(1)") if e.start >= 4300 * T else e
        for e in evs])
    assert rs.join(wrong) is None
    # a program never starts before its enqueue began: the leading block is
    # not request 1's chunk even where the families would allow it
    early = _edit(hand_made(), "/device:TPU:0", lambda evs: [
        prog("jit_prefill(2)", 500, 100)] + evs)
    assert [p.start // T for _, p in rs.join(early)[:2]] == [2020, 2300]
    # the count closes and the witness does not hold: no fetch returned as
    # the joined programs ended (requests nobody streams to)
    unfetched = _edit(hand_made(), "/host:CPU", lambda evs: [
        e for e in evs if e.name != FETCH_RANGE])
    assert next(rs.closing_drops(rs.programs(unfetched),
                                 rs.dispatches(unfetched))) == 1
    assert rs.join(unfetched) is None
    late = _edit(hand_made(), "/host:CPU", lambda evs: [
        e._replace(dur=e.dur + 1000 * T) if e.name == FETCH_RANGE else e
        for e in evs])
    assert rs.join(late) is None
    # no chip in the trace
    host_only = {"/host:CPU": hand_made()["/host:CPU"]}
    with pytest.raises(tr.NoDeviceTrace):
        rs.join(host_only)


def test_the_witness_chooses_where_more_than_one_number_closes():
    # six iterations alike, 4000 apart: a last chunk (program 300), a block
    # (2000).  Each range also meets the NEXT iteration's program of its
    # family, which started after it too; only the fetches tell
    host, chip = [Ev(tr.WINDOW_MARK, 1000 * T, 25000 * T, {})], \
        [prog("jit_body(1)", 0, 2900)]
    for i in range(6):
        at = 2000 + 4000 * i
        host += [chunk(2 * i + 1, i, 1, at + 100), block(2 * i + 2, at + 300),
                 fetch(at + 400, at + 1310)]
        chip += [prog("jit_prefill(2)", at + 1000, 300),
                 prog("jit_body(1)", at + 1300, 2000)]
    trace = {"/host:CPU": {"python3": host},
             "/device:TPU:0": {tr.MODULES_LINE: chip}}
    ranges, progs = rs.dispatches(trace), rs.programs(trace)
    drops = list(rs.closing_drops(progs, ranges))
    assert drops[:3] == [1, 3, 5]
    fetches = tr.host_events(trace, FETCH_RANGE)
    assert [rs.witnessed(list(zip(ranges, (p for _, p in progs[d:]))),
                         fetches) for d in drops[:3]] == [1.0, 0.0, 0.0]
    assert [p.start // T for _, p in rs.join(trace)[:2]] == [3000, 3300]
    assert len(rs.backlog_split(trace)) == 6


def test_a_request_preempted_between_two_last_chunks_is_left_out():
    trace = _edit(hand_made(), "/host:CPU", lambda evs: [
        e._replace(stats=dict(e.stats, request_id=1))
        if e.stats.get("seq") == 8 else e for e in evs])
    assert [s["request_id"] for s in rs.backlog_split(trace)] == [2]


def test_a_last_chunk_enqueued_outside_the_window_is_left_out():
    trace = _edit(hand_made(), "/host:CPU", lambda evs: [
        e._replace(start=1250 * T, dur=6000 * T)
        if e.name == tr.WINDOW_MARK else e
        for e in evs])
    assert [s["request_id"] for s in rs.backlog_split(trace)] == [3, 2]


# ---------------------------------------------------------------------------
# recorded on a v5e (my chip run, PR 38): two seconds from the middle of the
# traced window of mistral-7b-L8.serve-chat, cut by
# `python -m benchmarks.lib.request_spans <trace dir> --cut`: chip 0's
# programs and the host's ranges with their stats, no instruction
# ---------------------------------------------------------------------------

FIXTURES = os.path.join(TESTS_DIR, "fixtures")
JOIN_FIXTURE = os.path.join(FIXTURES, "v5e_serve_request_spans_2s.json.gz")
PARENT_FIXTURE = os.path.join(FIXTURES, "v5e_serve_spans_150ms.json.gz")


def test_recorded_v5e_trace_joins():
    trace = tr.load_events(JOIN_FIXTURE)
    assert set(trace["/device:TPU:0"]) == {tr.MODULES_LINE}
    joined = rs.join(trace)
    ran = [(r, p) for r, p in joined if p is not None]
    assert len(ran) >= len(joined) - 6       # the tail the chip had not run
    for r, p in ran:
        # the family its range names, and never before its enqueue began
        assert tr.program_name(p.name).startswith(
            "jit_prefill" if r.name == CHUNK_RANGE else "jit_body")
        assert p.start >= r.start
    starts = [p.start for _, p in ran]
    assert starts == sorted(starts)
    split = rs.backlog_split(trace)
    assert len(split) >= 10
    for s in split:
        assert sum(s[p] for p in rs.PARTS) == pytest.approx(s["interval"],
                                                            abs=1e-12)
        assert min(s[p] for p in rs.PARTS) >= 0
        assert s["own"] > 0 and s["program_starts_after_enqueue"] >= 0
    for part in rs.PARTS:
        assert rs.backlog_part_p50_ms(split, part) == pytest.approx(
            median([s[part] for s in split]) * 1e3)
    assert rs.backlog_part_p50_ms(split, "own",
                                  min_requests=len(split) + 1) is None
    # the chunk's own program: whole, and the part of it inside the
    # interval, the shorter where the chip was idle and it started at once
    for s in split:
        assert s["own"] <= s["program"] + 1e-12
        if s["own"] < s["program"] - 1e-9:
            assert s["decode"] == 0 and s["own"] == pytest.approx(
                s["interval"])
    behind = rs.behind_a_block(split)
    assert 0 < len(behind) < len(split)
    assert all(s["decode"] > 30e-3 for s in behind)      # most of a block
    assert rs.behind_block_share(split) == pytest.approx(
        100.0 * len(behind) / len(split))
    # the witness the join was held to: the host's blocking fetch of a first
    # token returns as its chunk's program ends (a join off by a program
    # would be off by a chunk or a block); one fewer or one more leading
    # program dropped and it does not hold
    ranges, progs = rs.dispatches(trace), rs.programs(trace)
    fetches = sorted(tr.host_events(trace, rs.FETCH), key=lambda e: e.start)
    drop = len(progs) - len(ran)
    shares = [rs.witnessed(list(zip(ranges, (p for _, p in progs[d:]))),
                           fetches) for d in (drop, drop + 1, drop + 2)]
    assert shares[0] >= rs.WITNESS_SHARE and max(shares[1:]) < 0.5
    # the ranges keep the names the idle attribution matches
    names = {e.name for evs in trace["/host:CPU"].values() for e in evs}
    assert {CHUNK_RANGE, BLOCK_RANGE, host_spans.STEP} <= names


def test_the_parents_ranges_carry_no_launch_number():
    trace = tr.load_events(PARENT_FIXTURE)
    assert tr.host_events(trace, CHUNK_RANGE)
    assert rs.dispatches(trace) is None
    assert rs.join(trace) is None and rs.backlog_split(trace) is None


@pytest.mark.parametrize("name", sorted(JOINED))
def test_joined_readers(name, monkeypatch):
    bench = Bench()
    part, behind_only = JOINED[name]
    rs._split_of_xplane.cache_clear()
    monkeypatch.setattr(rs.tr, "find_xplane", lambda d: JOIN_FIXTURE)
    monkeypatch.setattr(rs, "trace_of_xplane", tr.load_events)
    split = rs.backlog_split(tr.load_events(JOIN_FIXTURE))
    victim = split[0]["request_id"]
    records = [NS(req=NS(request_id=s["request_id"],
                         preemptions=int(s["request_id"] == victim)))
               for s in split] + [None]
    ctx = {"bench": bench, "workload": "mistral-7b-L8.serve-chat",
           "trace": {"busy_s": 1.0}, "loop": {"records": records}}
    # ... without the request the loop saw preempted
    kept = split[1:]
    over = rs.behind_a_block(kept) if behind_only else kept
    assert len(over) >= rs.MIN_REQUESTS
    want = (100.0 * len(rs.behind_a_block(kept)) / len(kept) if part is None
            else median([s[part] for s in over]) * 1e3)
    assert bench.reader(name).read(ctx) == pytest.approx(want)
    # no device trace was reduced (a CPU run): left out
    assert bench.reader(name).read(dict(ctx, trace=None)) is None
    # the parent's trace: left out
    rs._split_of_xplane.cache_clear()
    monkeypatch.setattr(rs.tr, "find_xplane", lambda d: PARENT_FIXTURE)
    assert bench.reader(name).read(ctx) is None
    rs._split_of_xplane.cache_clear()


def test_cut_keeps_programs_and_dispatch_ranges_whole(tmp_path):
    path = str(tmp_path / "cut.json.gz")
    rs.cut(hand_made(), path, 1250 * T, 7000 * T)
    back = tr.load_events(path)
    # request 1's range began before the cut and request 2's last program
    # ends after it: both are gone, nothing is shortened
    assert [e.stats["seq"] for e in tr.host_events(back, CHUNK_RANGE)] == \
        [7, 8, 10]
    assert [(e.start // T, e.dur // T)
            for e in back["/device:TPU:0"][tr.MODULES_LINE]
            ] == [(2000, 10), (2020, 280), (2300, 2000), (4300, 200),
                  (4500, 300), (4800, 2000)]
    # the other ranges: clipped
    assert tr.window_of(back) == (1250 * T, 7000 * T)
    assert [s["request_id"] for s in rs.backlog_split(back)] == [3]
