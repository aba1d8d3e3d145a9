import json
import os

import numpy as np
import pytest

from benchmarks.lib import traffic

from conftest import BENCH_DIR


def mix(name):
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


def same(a, b):
    return (len(a) == len(b) and all(
        x.due_s == y.due_s and x.max_new_tokens == y.max_new_tokens
        and np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b)))


@pytest.mark.parametrize("name, vocab", [("chat-1k", 50257),
                                         ("longprompt-8k", 32768)])
def test_serving_mix_is_a_pure_function_of_the_seed(name, vocab):
    m = mix(name)
    a = traffic.serve_schedule(m, 5.0, 40.0, 11, vocab)
    assert same(a, traffic.serve_schedule(m, 5.0, 40.0, 11, vocab))
    b = traffic.serve_schedule(m, 5.0, 40.0, 12, vocab)
    assert not same(a, b)
    # the mix carries its pattern: another seed, other tokens, same work
    assert [(x.due_s, len(x.prompt), x.max_new_tokens) for x in a] == \
        [(x.due_s, len(x.prompt), x.max_new_tokens) for x in b]
    # another pattern_seed is another realisation of the same work
    c = traffic.serve_schedule(dict(m, pattern_seed=m["pattern_seed"] + 1),
                               5.0, 40.0, 11, vocab)
    assert [x.due_s for x in a] != [x.due_s for x in c]
    assert sorted(len(x.prompt) for x in a) == \
        sorted(len(x.prompt) for x in c)


@pytest.mark.parametrize("name, rate, prompt_mean, out_mean", [
    # a lognormal's mean is median * exp(sigma^2 / 2), pulled in by the clips
    ("chat-1k", 50.0, (130, 165), (215, 260)),
    ("longprompt-8k", 50.0, (4300, 4800), (62, 76)),
])
def test_serving_mix_hits_its_clips_and_means(name, rate, prompt_mean,
                                              out_mean):
    m = mix(name)
    s = traffic.serve_schedule(m, rate, 60.0, 3, 1000)
    assert len(s) == rate * 60           # a fixed amount of work
    assert all(0 <= a.due_s < 60.0 for a in s)
    assert [a.due_s for a in s] == sorted(a.due_s for a in s)
    plen = np.array([len(a.prompt) for a in s])
    olen = np.array([a.max_new_tokens for a in s])
    p, o = m["prompt_tokens"], m["output_tokens"]
    assert plen.min() >= p["min"] and plen.max() <= p["max"]
    assert olen.min() >= o["min"] and olen.max() <= o["max"]
    assert (plen == p["max"]).any() and (plen == p["min"]).any()
    assert (plen + olen).max() <= m["max_total_tokens"]
    assert prompt_mean[0] < plen.mean() < prompt_mean[1]
    assert out_mean[0] < olen.mean() < out_mean[1]
    assert all(a.prompt.dtype == np.int32 and a.prompt.max() < 1000
               for a in s)


@pytest.mark.parametrize("change", [
    {"prompt_tokens": {"dist": "uniform", "min": 8, "max": 64}},
    {"arrivals": {"process": "gamma"}},
])
def test_a_law_the_generator_does_not_have_is_an_error(change):
    with pytest.raises(ValueError, match="unknown"):
        traffic.serve_schedule(dict(mix("chat-1k"), **change), 5.0, 4.0, 1,
                               1000)


def test_training_rows_are_seeded_zipf():
    m = mix("zipf-pack-1024")
    a = next(traffic.train_batches(m, 7, 50257, 8))
    b = next(traffic.train_batches(m, 7, 50257, 8))
    assert a.shape == (8, 1024) and a.dtype == np.int32
    assert np.array_equal(a, b)
    assert not np.array_equal(a, next(traffic.train_batches(m, 8, 50257, 8)))
    stream = traffic.train_batches(m, 7, 50257, 8)
    assert not np.array_equal(next(stream), next(stream))   # fresh each step
    # Zipf(1): rank 1 has probability 1 / H_V = 1 / 11.4
    assert abs((a == 0).mean() - 1 / 11.4) < 0.02
    # the law's entropy, where an i.i.d. stream's loss can fall to: the
    # 7.57 nats the mix file and PERF.md quote
    p = np.diff(traffic.token_law(m["tokens"], 50257), prepend=0.0)
    assert -(p * np.log(p)).sum() == pytest.approx(7.566, abs=1e-3)
