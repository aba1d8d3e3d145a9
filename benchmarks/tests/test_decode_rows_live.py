"""``decode_rows_live_share``: right on hand-made counters, ``None`` — never
a wrong value — for a program without ``ds_serve_decode_row_slots_total``
(the parent, whose attention kernels visit every slot), listed for the cells
that report ``tpot_p50_ms``, and printed by a traced tiny cell."""

import pytest

from benchmarks.lib.manifest import Bench
from benchmarks.run import run_cell

NAME = "decode_rows_live_share"
LIVE, SLOTS = "ds_serve_decode_tokens_total", \
    "ds_serve_decode_row_slots_total"


def ctx(begin, end):
    return {"counters": {"begin": begin, "trace_start": end}}


@pytest.mark.parametrize("begin,end,want", [
    # 40 blocks of 64 slots x 8 steps, 5,120 tokens scheduled
    ({LIVE: 100.0, SLOTS: 512.0}, {LIVE: 5220.0, SLOTS: 20992.0}, 25.0),
    # every slot decoding in every step: nothing to skip
    ({LIVE: 0.0, SLOTS: 0.0}, {LIVE: 1024.0, SLOTS: 1024.0}, 100.0),
    # the parent: tokens are counted, the batch's row slots are not
    ({LIVE: 100.0}, {LIVE: 5220.0}, None),
    # no block ran
    ({LIVE: 7.0, SLOTS: 512.0}, {LIVE: 7.0, SLOTS: 512.0}, None),
], ids=["quarter_live", "full_batch", "parent", "no_block"])
def test_reader_on_hand_made_counters(begin, end, want):
    got = Bench().reader(NAME).read(ctx(begin, end))
    assert got == (want if want is None else pytest.approx(want))


def test_reader_without_snapshots():
    # the registry is off outside the traced run
    assert Bench().reader(NAME).read({"counters": {}}) is None


def test_listed_for_the_cells_that_report_tpot():
    bench = Bench()
    (entry,) = [m for m in bench.manifest["per_layer"] if m["name"] == NAME]
    (tpot,) = [m for m in bench.manifest["end_to_end"]
               if m["name"] == entry["moves"] == "tpot_p50_ms"]
    assert entry["workloads"] == tpot["workloads"]
    assert (entry["layer"], entry["source"], entry["unit"]) == \
        ("Kernels", "program_counter", "%")


def test_traced_tiny_cell_prints_it(tiny_bench):
    line = run_cell("tiny-gpt2.serve", 11, 1.5, True,
                    manifest_path=tiny_bench, allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    share = line["metrics"][NAME]
    assert share["unit"] == "%" and 0 < share["value"] <= 100
    # (not bounded by sched_occupancy_mean: that one averages over every
    # iteration, this one over the iterations that ran a block)
    # an untraced run leaves the registry off: no counter, no value
    line = run_cell("tiny-gpt2.serve", 11, 1.5, False,
                    manifest_path=tiny_bench, allow_cpu=True)
    assert NAME not in line["metrics"]
