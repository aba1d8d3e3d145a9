import json
import os

import pytest

from benchmarks.lib import costs
from benchmarks.lib.peaks import peaks

from conftest import BENCH_DIR


def model_config(name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)["model_config"]


def test_v5e_peaks_and_unknown_device():
    pk = peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    assert pk["ici_bytes_per_s"] == 200e9          # 1,600 Gbit/s
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks("cpu")


def test_train_flops_per_token_gpt2_xl():
    mc = model_config("gpt2-xl")
    # 48 layers x 12 x 1600^2 + 50257 x 1600 head = 1.555e9 matmul weights
    assert costs.matmul_params(mc) == 48 * 12 * 1600 ** 2 + 50257 * 1600
    want = 6 * costs.matmul_params(mc) + 6 * 48 * 1600 * 1024
    assert costs.train_flops_per_token(mc, 1024) == want
    assert 9.7e9 < want < 9.9e9


def test_attention_kernel_costs():
    # one head, one row: forward is QK^T and PV, 2 x 2 x S^2 x Dh, halved
    assert costs.flash_attention_flops("flash_attention_fwd", 1, 1, 1024,
                                       64) == 2 * 2 * 1024 ** 2 * 64 * 0.5
    assert costs.flash_attention_flops("flash_attention_bwd_dkv", 8, 25,
                                       1024, 64) == \
        4 * 2 * 8 * 25 * 1024 ** 2 * 64 * 0.5
    # Mistral: 8 KV heads x 128 x (K and V) x 2 bytes = 4 KiB a token a layer
    assert costs.decode_attention_bytes(model_config("mistral-7b-L8"),
                                        1000) == 1000 * 4096
    # GPT-2 XL: 25 x 64 x 2 x 2 = 6,400 bytes a token a layer
    assert costs.decode_attention_bytes(model_config("gpt2-xl"), 1) == 6400


def test_roofline_says_which_bound():
    pk = peaks("TPU v5 lite")
    assert costs.least_seconds(197e12, 1.0, pk) == (1.0, "flops")
    assert costs.least_seconds(1.0, 819e9, pk) == (1.0, "bytes")
