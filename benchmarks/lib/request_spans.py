"""Where a first token's time in its slot goes: the reductions behind
``ttft_chunk_wait_p50_ms``, ``ttft_chunks_p50_ms``, ``ttft_backlog_p50_ms``
(the program's stamps), ``prefill_turns_missed_share``,
``first_token_overlapped_share`` (its counters) and ``last_chunk_behind_
block_share``, ``ttft_backlog_decode_p50_ms``, ``ttft_backlog_chunks_p50_ms``,
``ttft_own_chunk_p50_ms`` (the trace).

``lib/host_spans.ttft_parts`` takes ``prefill`` as one difference of
stamps, slot to first token.  The serve engine stamps two instants in
between (``Request.t_first_chunk``: the request's turn came;
``t_last_chunk``: its last chunk's program was enqueued), so ``prefill`` =
``chunk_wait`` + ``chunks`` + ``backlog``, exactly.  ``backlog`` is a wait
for the chip: for whatever was queued ahead of that last chunk, and for the
chunk itself.  What ran there is read from the profiler's trace.  Every
chunk program and every decode block the engine enqueues has a launch
number, and the host range around its enqueue (``ds_serve_prefill_dispatch``,
``ds_serve_decode_dispatch``) carries it as the stat ``seq``, a chunk's with
``request_id`` and ``last``.  The chip runs those programs in the order they
were enqueued (``jit_prefill*`` and ``jit_body*`` on the ``XLA Modules``
line), so the n-th range is the n-th program, once the programs enqueued
before the profiler started are set aside: :func:`join`, which holds each
candidate to a witness it does not join by, the host's blocking fetch of a
first token returning as its chunk's program ends.

A program older than the stamps, the stats or the counters gives None
everywhere here, and the reader leaves its metric out.

    python -m benchmarks.lib.request_spans <trace dir, .xplane.pb or a cut>
        [--cut <out.json.gz> [--ms 1000]]

prints the split request by request.  ``--cut`` also writes that many
milliseconds from the middle of the trace as a test fixture: chip 0's
``XLA Modules`` line and the ``bench_*`` / ``ds_serve_*`` host ranges with
their stats, no instruction.
"""

from __future__ import annotations

import functools
import sys
from typing import Any, Dict, Iterable, List, Optional, Tuple

from benchmarks.lib import host_spans
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.moe_counters import grown
from benchmarks.lib.stats import median

CHUNK, BLOCK = "chunk", "block"
DISPATCH = {"ds_serve_prefill_dispatch": CHUNK,
            "ds_serve_decode_dispatch": BLOCK}
# the two program families that are the chip's time, by what jit names them
# after (``ServingEngine._prefill_fn``'s ``prefill``, ``_block``'s ``body``)
FAMILY = (("jit_prefill", CHUNK), ("jit_body", BLOCK))
FETCH = host_spans.BLOCKING[0]          # ds_serve_first_token_fetch
HOST_PLANE = r"^/host:CPU$"
CHIP_0 = r"^/device:TPU:0$"
HOST_RANGES = (host_spans.PREFIX, "bench_")
MIN_REQUESTS = 8
PARTS = ("decode", "chunks", "own", "idle")
# a first token's fetch returns this long after its chunk's program ended,
# at most (read: 0.9-2.8 ms, my chip runs, PR 38; a join off by one program
# is off by a chunk or a block, 6-100 ms), for this share of the last chunks
# at least (the host comes late to a few fetches, the trace cuts one off)
WITNESS_NS = 5e6
WITNESS_SHARE = 0.9
TURNS = ("ds_serve_prefill_turns_missed_total", "ds_serve_prefill_turns_total")
FIRSTS = ("ds_serve_first_token_overlapped_total",
          "ds_serve_first_tokens_total")


# ---------------------------------------------------------------------------
# the stamps: prefill = chunk_wait + chunks + backlog
# ---------------------------------------------------------------------------

def taken_requests(ctx: Dict[str, Any]) -> Optional[List[Any]]:
    """The ``Request`` behind each entry of ``host_spans.ttft_parts``, in
    its order; None where it gives None.  The rule that takes a request
    lives there alone and hands out no request, so each entry is met again
    by the two differences of stamps it holds (``queue``, ``prefill``),
    walking the records in the order it walked them."""
    parts = host_spans.ttft_parts(ctx)
    if parts is None:
        return None
    reqs = (lv.req for lv in ctx["loop"]["records"] if lv is not None)
    return [next(r for r in reqs
                 if (r.t_admit - r.t_submit, r.t_first_token - r.t_admit)
                 == (p["queue"], p["prefill"])) for p in parts]


def stamp_parts(ctx: Dict[str, Any]) -> Optional[List[Dict[str, float]]]:
    """Per request, in seconds: ``chunk_wait`` (slot -> its turn came for a
    first chunk), ``chunks`` (-> its last chunk's program enqueued: a wait
    for pages, the iterations between its chunks), ``backlog`` (-> first
    token's value on the host: the chip working off what was queued, the
    last chunk itself, the fetch), and ``prefill``, their sum to the bit
    and ``ttft_parts``' own.  The requests ``ttft_parts`` takes, in its
    order.  None where it gives None, or a request has no ``t_last_chunk``
    (the program is older than the stamp)."""
    reqs = taken_requests(ctx)
    if reqs is None or not all(getattr(r, "t_last_chunk", 0.0) for r in reqs):
        return None
    return [{"chunk_wait": r.t_first_chunk - r.t_admit,
             "chunks": r.t_last_chunk - r.t_first_chunk,
             "backlog": r.t_first_token - r.t_last_chunk,
             "prefill": r.t_first_token - r.t_admit} for r in reqs]


def stamp_part_p50_ms(ctx: Dict[str, Any], part: str) -> Optional[float]:
    parts = stamp_parts(ctx)
    return median([p[part] for p in parts]) * 1e3 if parts else None


# ---------------------------------------------------------------------------
# the counters, window begin to profiler start
# ---------------------------------------------------------------------------

def counter_share(ctx: Dict[str, Any], part: str, whole: str
                  ) -> Optional[float]:
    """Percent: growth of ``part`` over growth of ``whole``."""
    g = grown(ctx, part, whole)
    return 100.0 * g[part] / g[whole] if g and g[whole] else None


# ---------------------------------------------------------------------------
# the trace: which program on the chip a dispatch range enqueued
# ---------------------------------------------------------------------------

def _family(module_name: str) -> Optional[str]:
    name = tr.program_name(module_name)
    for prefix, kind in FAMILY:
        if name.startswith(prefix):
            return kind
    return None


def dispatches(trace: tr.Trace) -> Optional[List[tr.Ev]]:
    """Every dispatch range, in launch order.  None where no range carries
    ``seq`` (the program has no launch number) or the numbers the trace
    holds are not consecutive."""
    found = sorted((e for name in DISPATCH
                    for e in tr.host_events(trace, name) if "seq" in e.stats),
                   key=lambda e: int(e.stats["seq"]))
    seqs = [int(e.stats["seq"]) for e in found]
    if not seqs or seqs != list(range(seqs[0], seqs[0] + len(seqs))):
        return None
    return found


def programs(trace: tr.Trace) -> List[Tuple[str, tr.Ev]]:
    """``(kind, module event)`` of chip 0's chunk programs and decode
    blocks, in start order."""
    planes = tr.device_planes(trace)
    if not planes:
        raise tr.NoDeviceTrace("the trace has no /device:TPU plane")
    evs = sorted(trace[planes[0]].get(tr.MODULES_LINE, ()),
                 key=lambda e: e.start)
    return [(k, e) for k, e in ((_family(e.name), e) for e in evs) if k]


def join(trace: tr.Trace) -> Optional[List[Tuple[tr.Ev, Optional[tr.Ev]]]]:
    """``(dispatch range, its program on the chip)`` in launch order; the
    program is None for the last ranges, whose programs had not run when
    the profiler stopped.

    The chip's first programs were enqueued before the profiler started and
    have no range.  How many is not assumed from the depth of the engine's
    pipeline: it is the one number of leading programs to drop for which
    the count closes (:func:`closing_drops`) AND the witness holds
    (:func:`witnessed`, at least :data:`WITNESS_SHARE`).  None where no
    number does, or more than one: a count that does not close is not
    joined by guess."""
    ranges = dispatches(trace)
    if ranges is None:
        return None
    progs = programs(trace)
    fetches = sorted(tr.host_events(trace, FETCH), key=lambda e: e.start)
    held = []
    for drop in closing_drops(progs, ranges):
        ran = [p for _, p in progs[drop:]]
        pairs = list(zip(ranges, ran + [None] * (len(ranges) - len(ran))))
        if witnessed(pairs, fetches) >= WITNESS_SHARE:
            held.append(pairs)
    return held[0] if len(held) == 1 else None


def closing_drops(progs, ranges):
    """The numbers of leading programs after whose removal every program
    left has a range of its own family that began before it did, least
    first (never all of them: nothing would be joined)."""
    for drop in range(len(progs)):
        rest = progs[drop:]
        if len(rest) <= len(ranges) and all(
                kind == DISPATCH[r.name] and p.start >= r.start
                for (kind, p), r in zip(rest, ranges)):
            yield drop


def _is_last_chunk(r: tr.Ev) -> bool:
    return DISPATCH[r.name] == CHUNK and bool(int(r.stats.get("last", 0)))


def witnessed(pairs, fetches: List[tr.Ev]) -> float:
    """Of the last chunks of ``pairs`` that have a program and a fetch in
    the trace, the share whose first token's fetch
    (``ds_serve_first_token_fetch``, blocking) returned within
    :data:`WITNESS_NS` after the program ended, and not before: the value
    is the program's output.  The engine fetches in the order it enqueued
    (``_owed`` is a queue), so a last chunk's fetch is the first that began
    after its enqueue ended and is not an earlier chunk's.  0.0 where there
    is none to ask: requests whose first token nobody waits for (no stream,
    no EOS id) are fetched at their finish, and their trace is not joined."""
    asked = held = i = 0
    for r, p in pairs:
        if p is None or not _is_last_chunk(r):
            continue
        while i < len(fetches) and fetches[i].start < r.start + r.dur:
            i += 1
        if i == len(fetches):
            break
        late = fetches[i].start + fetches[i].dur - (p.start + p.dur)
        asked, held, i = asked + 1, held + (0 <= late <= WITNESS_NS), i + 1
    return held / asked if asked else 0.0


def _inside(evs: Iterable[tr.Ev], lo: float, hi: float) -> float:
    return sum(max(0, min(e.start + e.dur, hi) - max(e.start, lo))
               for e in evs)


def backlog_split(trace: tr.Trace) -> Optional[List[Dict[str, float]]]:
    """For each request whose LAST chunk was enqueued inside the traced
    window (and whose program ran before the profiler stopped), the interval
    from that enqueue's end to the END of its own chunk program on chip 0,
    in seconds, and what the chip did in it: ``decode`` (decode blocks),
    ``chunks`` (other chunk programs), ``own`` (its own), ``idle`` (the
    rest: nothing ran, or one of the small programs that have no launch
    number).  Programs of one chip do not overlap, so the four sum to
    ``interval``: the trace's view of the stamps' ``backlog``, less the
    fetch.  ``program`` is the own program's whole length: on an idle chip
    it starts while its enqueue is still returning, and ``own`` is then the
    shorter.  A request with two last chunks in the trace was preempted in
    between and is left out.  None where :func:`join` gives None."""
    joined = join(trace)
    if joined is None:
        return None
    lo, hi = tr.window_of(trace)
    # what ran: every program of the two families, those enqueued before
    # the profiler started too
    progs = programs(trace)
    blocks = [p for k, p in progs if k == BLOCK]
    chunks = [p for k, p in progs if k == CHUNK]
    lasts = [(r, p) for r, p in joined if _is_last_chunk(r)]
    seen: Dict[int, int] = {}
    for r, _ in lasts:
        rid = int(r.stats["request_id"])
        seen[rid] = seen.get(rid, 0) + 1
    out = []
    for r, p in lasts:
        rid = int(r.stats["request_id"])
        t0 = r.start + r.dur
        if p is None or not lo <= t0 < hi or seen[rid] > 1:
            continue
        t1 = p.start + p.dur
        part = {"decode": _inside(blocks, t0, t1),
                "chunks": _inside((c for c in chunks if c is not p), t0, t1),
                "own": _inside((p,), t0, t1)}
        part["idle"] = (t1 - t0) - sum(part.values())
        out.append({"request_id": rid, "seq": int(r.stats["seq"]),
                    "interval": (t1 - t0) / 1e9, "program": p.dur / 1e9,
                    "program_starts_after_enqueue": (p.start - r.start) / 1e9,
                    **{k: v / 1e9 for k, v in part.items()}})
    return out


def behind_a_block(split: List[Dict[str, float]]) -> List[Dict[str, float]]:
    """Of :func:`backlog_split`'s requests, those whose last chunk waited
    behind a decode block.  The others were enqueued on a chip that had
    nothing of the two families left to run (the host had just waited the
    block out, for its tokens or for pages) and read ``decode`` 0: two
    kinds at near even odds in the chat cells, so a median over both says
    which kind won the run, and each is read apart."""
    return [s for s in split if s["decode"] > 0]


def backlog_part_p50_ms(split: Optional[List[Dict[str, float]]], part: str,
                        min_requests: int = MIN_REQUESTS) -> Optional[float]:
    """Median of one part over the given requests, in ms; None under
    ``min_requests`` of them."""
    if not split or len(split) < min_requests:
        return None
    return median([s[part] for s in split]) * 1e3


def behind_block_share(split: Optional[List[Dict[str, float]]],
                       min_requests: int = MIN_REQUESTS) -> Optional[float]:
    """Percent of the joined requests whose last chunk waited behind a
    decode block; None under ``min_requests`` of them."""
    if not split or len(split) < min_requests:
        return None
    return 100.0 * len(behind_a_block(split)) / len(split)


# ---------------------------------------------------------------------------
# a run's trace
# ---------------------------------------------------------------------------

def trace_of_xplane(path: str) -> tr.Trace:
    """What :func:`join` reads of an ``.xplane.pb``: the host plane with
    its events' stats, and chip 0's ``XLA Modules`` line.  Two loads:
    ``trace_reduce.load_xplane`` keeps a host event's stats only together
    with every stat of every device instruction it is asked for."""
    chip = tr.load_xplane(path, planes=(CHIP_0,))
    return {**tr.load_xplane(path, planes=(HOST_PLANE,), all_stats=True),
            **{plane: {tr.MODULES_LINE: lines.get(tr.MODULES_LINE, [])}
               for plane, lines in chip.items()}}


@functools.lru_cache(maxsize=2)
def _split_of_xplane(path: str) -> Optional[Tuple[Dict[str, float], ...]]:
    trace = trace_of_xplane(path)
    if not tr.device_planes(trace):
        return None
    split = backlog_split(trace)
    return None if split is None else tuple(split)


def split_of_run(ctx: Dict[str, Any]) -> Optional[List[Dict[str, float]]]:
    """:func:`backlog_split` of this run's trace, loaded once per run,
    without the requests the loop saw preempted; None where the run has no
    device trace."""
    if ctx.get("trace") is None:
        return None
    path = tr.find_xplane(ctx["bench"].path("out", "trace", ctx["workload"]))
    split = _split_of_xplane(path) if path else None
    if split is None:
        return None
    preempted = {lv.req.request_id for lv in ctx["loop"]["records"]
                 if lv is not None and lv.req.preemptions}
    return [s for s in split if s["request_id"] not in preempted]


def run_backlog_p50_ms(ctx: Dict[str, Any], part: str,
                       behind_only: bool = False) -> Optional[float]:
    split = split_of_run(ctx)
    if split and behind_only:
        split = behind_a_block(split)
    return backlog_part_p50_ms(split, part)


def table(split: List[Dict[str, float]]) -> str:
    ms = lambda s: f"{s * 1e3:9.3f}"
    out = [f"{len(split)} requests whose last chunk was enqueued in the "
           "window and ran in the trace; ms from that enqueue's end to the "
           "end of the chunk's program on chip 0",
           f"{'request':>8s} {'seq':>7s} {'interval':>9s} "
           + " ".join(f"{p:>9s}" for p in PARTS)]
    for s in split:
        out.append(f"{s['request_id']:8d} {s['seq']:7d} {ms(s['interval'])} "
                   + " ".join(ms(s[p]) for p in PARTS))
    behind = behind_a_block(split)
    for label, rows in (("median", split), ("behind a block", behind),
                        ("on an idle chip",
                         [s for s in split if s not in behind])):
        if rows:
            out.append(f"{label + ' (' + str(len(rows)) + ')':>16s} "
                       + " ".join(ms(median([s[p] for s in rows]))
                                  for p in ("interval",) + PARTS))
    if split:
        out.append("own program, whole: "
                   + ms(median([s["program"] for s in split])).strip())
    return "\n".join(out)


def cut(trace: tr.Trace, path: str, lo: float, hi: float) -> None:
    """Write ``[lo, hi)`` of :func:`trace_of_xplane`'s trace as a fixture:
    chip 0's programs and the host's ``bench_*`` / ``ds_serve_*`` ranges.
    ``save_events`` clips an event that crosses an edge; a program or a
    dispatch range cut short would say a wrong end or meet a wrong partner,
    so those two are kept whole or not at all."""
    whole = lambda e: lo <= e.start and e.start + e.dur <= hi
    keep = lambda device, e: whole(e) if device or e.name in DISPATCH \
        else e.name.startswith(HOST_RANGES)
    tr.save_events(
        {plane: {line: [e for e in evs
                        if keep(bool(tr.DEVICE_PLANE.match(plane)), e)]
                 for line, evs in lines.items()}
         for plane, lines in trace.items()}, path, lo, hi)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--cut", help="also write a fixture (.json.gz) here")
    ap.add_argument("--ms", type=float, default=1000.0)
    args = ap.parse_args(argv)
    saved = args.trace.endswith(".json.gz")     # what --cut wrote, again
    path = args.trace if saved or args.trace.endswith(".pb") \
        else tr.find_xplane(args.trace)
    if path is None:
        print(f"no .xplane.pb under {args.trace}", file=sys.stderr)
        return 1
    trace = tr.load_events(path) if saved else trace_of_xplane(path)
    try:
        split = backlog_split(trace)
    except tr.NoDeviceTrace as e:
        print(str(e), file=sys.stderr)
        return 1
    if split is None:
        print("the dispatch ranges carry no launch number, or not one "
              "number of leading programs closes the count with the "
              "first-token fetches as witness", file=sys.stderr)
        return 1
    ranges, progs = dispatches(trace), programs(trace)
    fetches = sorted(tr.host_events(trace, FETCH), key=lambda e: e.start)
    print(f"{len(ranges)} dispatch ranges, {len(progs)} chunk programs and "
          "decode blocks on chip 0; leading programs dropped -> share of "
          "last chunks whose fetch bears the join out:")
    for drop in list(closing_drops(progs, ranges))[:6]:
        ran = [p for _, p in progs[drop:]]
        print(f"  {drop}: {witnessed(list(zip(ranges, ran)), fetches):.3f}")
    print(table(split))
    if args.cut:
        lo, hi = tr.window_of(trace)
        lo, hi = ((lo + hi) / 2 - args.ms * 5e5, (lo + hi) / 2 + args.ms * 5e5)
        cut(trace, args.cut, lo, hi)
        again = backlog_split(tr.load_events(args.cut))
        if again is None:
            print(f"{args.cut}: the cut does not join", file=sys.stderr)
            return 1
        print(f"{args.cut}: {len(again)} requests")
    return 0


if __name__ == "__main__":
    sys.exit(main())
