"""``prefill_chunks_extra_share``: the growth of the extra chunks over the
growth of all chunks, window begin to profiler start; 0 where no request
was given a second chunk in an iteration, ``None`` — never a wrong value —
for a program without the counter (the parent), and listed, last, for the
three cells that report ``ttft_p50_ms``."""

import pytest

from benchmarks.lib.manifest import Bench

NAME = "prefill_chunks_extra_share"
EXTRA, CHUNKS = ("ds_serve_prefill_chunks_extra_total",
                 "ds_serve_prefill_chunks_total")


def counters(begin, at):
    return {"counters": {"begin": begin, "trace_start": at,
                         "end": {k: 10 * v for k, v in at.items()}}}


@pytest.mark.parametrize("ctx,want", [
    # 200 chunk programs in the window, 90 of them a request's second
    (counters({CHUNKS: 100, EXTRA: 10}, {CHUNKS: 300, EXTRA: 100}), 45.0),
    # every place went to another request: the parent's schedule
    (counters({CHUNKS: 100, EXTRA: 10}, {CHUNKS: 300, EXTRA: 10}), 0.0),
    # the parent's program: chunks counted, no extra counter
    (counters({CHUNKS: 100}, {CHUNKS: 300}), None),
    # no chunk program in the window
    (counters({CHUNKS: 100, EXTRA: 10}, {CHUNKS: 100, EXTRA: 10}), None),
], ids=["some", "none_extra", "parent", "no_chunks"])
def test_reader_on_made_up_counters(ctx, want):
    got = Bench().reader(NAME).read(ctx)
    assert got == (want if want is None else pytest.approx(want))


def test_listed_last_for_the_cells_that_report_ttft():
    bench = Bench()
    assert bench.manifest["per_layer"][-1] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Serve engine",
        "moves": "ttft_p50_ms",
        "workloads": ["mistral-7b-L8.serve-chat",
                      "olmoe-1b-7b-L8.serve-chat", "evabyte-L6.serve-doc"]}
    (ttft,) = [m for m in bench.manifest["end_to_end"]
               if m["name"] == "ttft_p50_ms"]
    assert bench.manifest["per_layer"][-1]["workloads"] == ttft["workloads"]
