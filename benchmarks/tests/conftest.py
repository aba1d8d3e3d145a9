"""The benchmark's own tests: CPU, four virtual devices, tiny sizes.

    python -m pytest benchmarks/tests

A CPU run shows that paths, control flow and counts are right; it never
yields a time or a rate that is written down anywhere.
"""

import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import pytest

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(TESTS_DIR)
REPO_DIR = os.path.dirname(BENCH_DIR)
if REPO_DIR not in sys.path:
    sys.path.insert(0, REPO_DIR)


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


TINY_GPT2 = dict(vocab_size=503, hidden_size=64, intermediate_size=256,
                 num_layers=2, num_heads=4, max_seq_len=128)
TINY_MISTRAL = dict(vocab_size=503, hidden_size=64, intermediate_size=160,
                    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                    max_seq_len=256)


def make_tiny_bench(tmp_path):
    """A benchmark directory of its own in ``tmp_path``: the real drivers,
    readers and references, tiny configurations, a tiny mix and one tiny
    cell of each kind.  Returns the manifest's path."""
    root = tmp_path / "tinybench"
    for d in ("drivers", "layer_metrics"):
        shutil.copytree(os.path.join(BENCH_DIR, d), root / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for d in ("configs", "workloads", "traffic", "reference"):
        (root / d).mkdir()
    shutil.copy(os.path.join(BENCH_DIR, "reference", "gpt2.py"),
                root / "reference" / "gpt2.py")
    shutil.copy(os.path.join(BENCH_DIR, "reference", "mistral.py"),
                root / "reference" / "mistral.py")

    cfg = _load(os.path.join(BENCH_DIR, "configs", "gpt2-xl.json"))
    cfg.update(n_embd=64, n_head=4, n_layer=2, vocab_size=503,
               n_positions=128)
    cfg["model_config"].update(TINY_GPT2)
    _dump(cfg, root / "configs" / "tiny-gpt2.json")
    cfg = _load(os.path.join(BENCH_DIR, "configs", "mistral-7b-L8.json"))
    cfg.update(hidden_size=64, intermediate_size=160, num_attention_heads=4,
               num_key_value_heads=2, num_hidden_layers=2, vocab_size=503)
    cfg["model_config"].update(TINY_MISTRAL)
    _dump(cfg, root / "configs" / "tiny-mistral.json")

    chat = _load(os.path.join(BENCH_DIR, "traffic", "chat-1k.json"))
    chat["prompt_tokens"].update(median=12, max=40)
    chat["output_tokens"].update(median=10, min=4, max=20)
    chat["max_total_tokens"] = 64
    _dump(chat, root / "traffic" / "chat-tiny.json")
    z = _load(os.path.join(BENCH_DIR, "traffic", "zipf-pack-1024.json"))
    z["seq_len"] = 32
    _dump(z, root / "traffic" / "zipf-tiny.json")

    serve = _load(os.path.join(BENCH_DIR, "workloads",
                               "mistral-7b-L8.serve-chat.json"))
    serve.update(name="tiny-gpt2.serve", config="tiny-gpt2",
                 traffic="chat-tiny", rate_rps=8.0, trace_seconds=0.5)
    serve["engine"].update(num_slots=4, prefill_chunk=16, max_out_tokens=64,
                           kv_pool_tokens=256, kv_page_tokens=16,
                           decode_block_tokens=4)
    _dump(serve, root / "workloads" / "tiny-gpt2.serve.json")
    train = _load(os.path.join(BENCH_DIR, "workloads",
                               "gpt2-xl.train-zero3.json"))
    train.update(name="tiny-gpt2.train", config="tiny-gpt2",
                 traffic="zipf-tiny", chips=4, trace_steps=1)
    train["ds_config"].update(train_batch_size=8,
                              train_micro_batch_size_per_gpu=2)
    train["ds_config"]["optimizer"]["params"]["lr"] = 1e-2
    _dump(train, root / "workloads" / "tiny-gpt2.train.json")

    m = _load(os.path.join(REPO_DIR, "BENCHMARK.json"))
    m["paths"] = ["tinybench"]
    m["configs"] = [
        {"name": "tiny-gpt2", "source": "test", "reduced": [], "why": "test",
         "file": "tinybench/configs/tiny-gpt2.json"}]
    m["workloads"] = [
        {"name": "tiny-gpt2.serve", "config": "tiny-gpt2",
         "traffic": "chat-tiny", "chips": 1, "why": "test"},
        {"name": "tiny-gpt2.train", "config": "tiny-gpt2",
         "traffic": "zipf-tiny", "chips": 4, "why": "test"}]
    rename = {"mistral-7b-L8.serve-chat": "tiny-gpt2.serve",
              "gpt2-xl.train-zero3": "tiny-gpt2.train"}
    for section in ("end_to_end", "per_layer"):
        for e in m[section]:
            if "workloads" in e:
                e["workloads"] = [rename[w] for w in e["workloads"]
                                  if w in rename]
    path = tmp_path / "BENCHMARK.json"
    _dump(m, path)
    return str(path)


@pytest.fixture
def tiny_bench(tmp_path):
    return make_tiny_bench(tmp_path)
