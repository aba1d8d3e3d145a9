"""``lib/loop_costs.py`` against counts made by hand at Ouro-2.6B's widths
(16 x 128 MHA, hidden 2,048, MLP 5,632, twelve layers run four times), and
at Mistral's, whose stack runs once."""

import os

import pytest

from benchmarks.lib import loop_costs

from conftest import BENCH_DIR, _load

OURO = _load(os.path.join(BENCH_DIR, "configs",
                          "ouro-2.6b-L12.json"))["model_config"]
MISTRAL = _load(os.path.join(BENCH_DIR, "configs",
                             "mistral-7b-L8.json"))["model_config"]


@pytest.mark.parametrize("mc,layers,kv_bytes", [
    # 48 cache layers x (K + V) x 16 heads x 128 x 2 B = 384 KB a token
    (OURO, 48, 393216),
    # no pass count: the stack runs once; GQA's 8 heads: 4 KB a layer
    (MISTRAL, 8, 32768)])
def test_cache_layers_and_bytes_a_token(mc, layers, kv_bytes):
    assert loop_costs.cache_layers(mc) == layers
    assert loop_costs.kv_bytes_per_token(mc) == kv_bytes
    assert loop_costs.kv_bytes_per_token(mc, 1) * 2 == kv_bytes


def test_the_pool_and_the_weights_as_the_configuration_file_reckons_them():
    # 16 slots x 1,280 tokens = 80 pages of 256: 8.05 GB
    assert loop_costs.kv_bytes_per_token(OURO) * 20480 == 8053063680
    # a layer: 4 x 2048^2 + 3 x 2048 x 5632 + four norms = 51.39 M parameters
    assert loop_costs.layer_weight_bytes(OURO) == 2 * 51388416
    assert 12 * loop_costs.layer_weight_bytes(OURO) == 1233321984  # 1.23 GB
    # a decode step reads them once a pass: 4.93 GB, 6.0 ms at 819 GB/s
    assert loop_costs.decode_step_weight_bytes(OURO) == 4 * 1233321984
    assert loop_costs.decode_step_weight_bytes(OURO) / 819e9 == \
        pytest.approx(6.02e-3, rel=1e-2)
    # two norms a layer without the post-norms, and no gate matrix in GPT-2
    plain = dict(OURO, sandwich_norm=False)
    assert loop_costs.layer_weight_bytes(OURO) \
        - loop_costs.layer_weight_bytes(plain) == 2 * 2 * 2048
    assert loop_costs.layer_weight_bytes(dict(plain, glu=False)) == \
        2 * (4 * 2048 ** 2 + 2 * 2048 * 5632 + 2 * 2048)
