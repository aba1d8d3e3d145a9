"""``attn_keys_fetched_fill_share``: right on hand-made counters, ``None`` —
never a wrong value — for a program without the two ``ds_serve_attn_keys_*``
counters (the parent, whose kernel fetches every page whole and counts
neither), listed for the cells whose decode steps read per-head K/V pages
under one page table."""

import pytest

from benchmarks.lib.manifest import Bench

NAME = "attn_keys_fetched_fill_share"
CELLS = ["ouro-2.6b-L12.serve-reason-768", "mistral-7b-L8.serve-chat",
         "olmoe-1b-7b-L8.serve-chat", "solar-open2-L4-ep8.serve-reason-4k"]
ATTENDED, FETCHED = "ds_serve_attn_keys_attended_total", \
    "ds_serve_attn_keys_fetched_total"


def ctx(begin, end):
    return {"counters": {"begin": begin, "trace_start": end}}


@pytest.mark.parametrize("begin,end,want", [
    # a row at 300 of a page of 256: 301 attended, 256 + 64 fetched
    ({ATTENDED: 0.0, FETCHED: 0.0}, {ATTENDED: 301.0, FETCHED: 320.0},
     100.0 * 301 / 320),
    # the window's growth, not the totals
    ({ATTENDED: 1000.0, FETCHED: 2000.0},
     {ATTENDED: 1000.0 + 9.3e6, FETCHED: 2000.0 + 9.8e6}, 100.0 * 9.3 / 9.8),
    # every row at a piece's last token
    ({ATTENDED: 5.0, FETCHED: 5.0}, {ATTENDED: 133.0, FETCHED: 133.0}, 100.0),
    # the parent: it counts neither
    ({"ds_serve_decode_tokens_total": 50.0},
     {"ds_serve_decode_tokens_total": 2459.0}, None),
    # no block ran
    ({ATTENDED: 7.0, FETCHED: 64.0}, {ATTENDED: 7.0, FETCHED: 64.0}, None),
], ids=["one_row", "growth", "full_pieces", "parent", "no_block"])
def test_reader_on_hand_made_counters(begin, end, want):
    got = Bench().reader(NAME).read(ctx(begin, end))
    assert got == (want if want is None else pytest.approx(want))


def test_reader_without_snapshots():
    # the registry is off outside the traced run
    assert Bench().reader(NAME).read({"counters": {}}) is None


def test_listed_for_the_cells_that_read_per_head_pages():
    bench = Bench()
    entries = [m for m in bench.manifest["per_layer"] if m["name"] == NAME]
    assert len(entries) == 1        # by membership, wherever it stands
    (entry,) = entries
    assert sorted(entry["workloads"]) == sorted(CELLS)
    assert (entry["layer"], entry["source"], entry["unit"], entry["better"],
            entry["moves"]) == ("Kernels", "program_counter", "%", "higher",
                                "tpot_p50_ms")
    (tpot,) = [m for m in bench.manifest["end_to_end"]
               if m["name"] == "tpot_p50_ms"]
    assert all(cell in tpot["workloads"] for cell in CELLS)
    cells = {w["name"] for w in bench.manifest["workloads"]}
    assert set(CELLS) <= cells


def test_the_counts_follow_the_kernels_own_rule():
    """The reader's two counters are moved by ``serving/cache_kind.py`` from
    ``ops/pallas/decode.py:paged_keys_fetched``: at the cells' shape (pages
    of 256, head dim 128) a row at ``pos`` fetches its whole pages and the
    last one's pieces of 64, and the share over a decode of 64 steps from a
    page boundary is what the arithmetic gives."""
    import numpy as np

    from deepspeed_tpu.ops.pallas.decode import paged_keys_fetched

    p = np.arange(512, 576)
    fetched = paged_keys_fetched(p, 256, 128)
    assert set(fetched) == {576}
    got = Bench().reader(NAME).read(ctx(
        {ATTENDED: 0.0, FETCHED: 0.0},
        {ATTENDED: float((p + 1).sum()), FETCHED: float(fetched.sum())}))
    assert got == pytest.approx(100.0 * (p + 1).sum() / (64 * 576))
