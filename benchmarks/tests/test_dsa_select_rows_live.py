"""``dsa_select_rows_live_share``: right on hand-made counters, ``None`` —
never a wrong value — for a program without
``ds_serve_dsa_rows_gathered_total`` (the parent, whose selection works
every slot), listed for the one cell whose model has an indexer."""

import pytest

from benchmarks.lib.manifest import Bench

NAME = "dsa_select_rows_live_share"
CELL = "dots3-note-L5-ep16.serve-doc-48k"
LIVE, GATHERED = "ds_serve_decode_tokens_total", \
    "ds_serve_dsa_rows_gathered_total"


def ctx(begin, end):
    return {"counters": {"begin": begin, "trace_start": end}}


@pytest.mark.parametrize("begin,end,want", [
    # 330 steps at 7.3 live rows, a group of four: 9.3 rows worked a step
    ({LIVE: 50.0, GATHERED: 64.0}, {LIVE: 2459.0, GATHERED: 3136.0},
     100.0 * 2409 / 3072),
    # every group full
    ({LIVE: 0.0, GATHERED: 0.0}, {LIVE: 1024.0, GATHERED: 1024.0}, 100.0),
    # the parent: tokens are counted, the selection's rows are not
    ({LIVE: 50.0}, {LIVE: 2459.0}, None),
    # no block ran
    ({LIVE: 7.0, GATHERED: 64.0}, {LIVE: 7.0, GATHERED: 64.0}, None),
], ids=["padded_groups", "full_groups", "parent", "no_block"])
def test_reader_on_hand_made_counters(begin, end, want):
    got = Bench().reader(NAME).read(ctx(begin, end))
    assert got == (want if want is None else pytest.approx(want))


def test_reader_without_snapshots():
    # the registry is off outside the traced run
    assert Bench().reader(NAME).read({"counters": {}}) is None


def test_listed_for_the_indexed_cell_only():
    bench = Bench()
    (entry,) = [m for m in bench.manifest["per_layer"] if m["name"] == NAME]
    assert entry["workloads"] == [CELL]
    assert (entry["layer"], entry["source"], entry["unit"], entry["better"],
            entry["moves"]) == ("Model", "program_counter", "%", "higher",
                                "tpot_p50_ms")
    (tpot,) = [m for m in bench.manifest["end_to_end"]
               if m["name"] == "tpot_p50_ms"]
    assert CELL in tpot["workloads"]
