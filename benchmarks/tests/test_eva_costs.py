"""``lib/eva_costs.py`` against counts made by hand at EvaByte's widths
(window 2048, chunk 16, 32 heads of 128)."""

import pytest

from benchmarks.lib import eva_costs

MC = dict(hidden_size=4096, num_heads=32, head_dim=128, num_layers=6,
          eva_window=2048, eva_chunk=16)


@pytest.mark.parametrize("pos,want", [
    (0, (1, 0)), (2047, (2048, 0)), (2048, (1, 128)), (4095, (2048, 128)),
    (4096, (1, 256)), (7000, (7000 - 6144 + 1, 3 * 128)),
    (15359, (15359 - 14336 + 1, 7 * 128))])
def test_attended_rows(pos, want):
    assert eva_costs.attended_rows(MC, pos) == want


@pytest.mark.parametrize("pos,n", [(0, 8), (2040, 8), (2044, 8), (2047, 1),
                                   (6143, 2), (4090, 5000)])
def test_span_is_the_sum_of_its_steps(pos, n):
    steps = [eva_costs.attended_rows(MC, p) for p in range(pos, pos + n)]
    assert eva_costs.attended_rows_span(MC, pos, n) == \
        (sum(w for w, _ in steps), sum(s for _, s in steps))


def test_bytes_and_flops_by_hand():
    # a row: K and V, 32 heads x 128 x 2 bytes each = 16 KB a layer
    assert eva_costs.row_bytes(MC) == 16384
    # a query at position 7000 attends 857 + 384 rows: 20.3 MB a layer
    assert eva_costs.decode_attention_bytes(MC, 857 + 384) == 1241 * 16384
    # pooling a window: 2048 rows read, 128 written
    assert eva_costs.summarize_bytes(MC) == (2048 + 128) * 16384
    # two pooling logits and two weighted sums a row and head, 2 x 128 each
    assert eva_costs.summarize_flops(MC) == 8 * 2048 * 32 * 128
