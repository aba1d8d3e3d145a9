"""The one general traffic generator.  A mix is a data file of parameters
(``traffic/<mix>.json``); everything here is a pure function of that file,
the cell's rate and ``--seed``, so the same seed gives the same inputs and
a later PR adds a mix of a kind that is here by adding a data file.

A fixed amount of work.  A window holds exactly ``round(rate * seconds)``
requests, and their lengths are the law's own quantiles at the midpoints
of that many equal strata, shuffled: every run of a cell offers the same
multiset of lengths, so a run differs from the next by the system and not
by the draw.  The arrival times, the lengths and their order come from the
mix's ``pattern_seed`` and are the same for every ``--seed``; the seed
chooses the token ids (and the weights).  That is one realisation of the
arrival process, kept with the mix as data: with some tens of requests a
window, a median over another realisation is another number, and a bound
of a few percent could never hold.

A serving mix (``"kind": "serve_open_loop"``) sets ``pattern_seed``,
``prompt_tokens`` and ``output_tokens`` (a length law each: ``{"dist":
"lognormal", "median", "sigma", "min", "max"}``), ``max_total_tokens`` (the
cap on prompt + output; the output gives way) and ``arrivals``
(``{"process": "poisson"}``).  Every prompt is distinct.  Bursts, shared
prefixes, re-asked prompts and mixtures of mixes are not here: no cell
uses one yet, and the PR that adds such a cell brings the schedule it
needs as a driver of its own (``drivers/<kind>.py``).

A training mix (``"kind": "train_steps"``) sets ``seq_len`` and ``tokens``:
``{"dist": "zipf", "exponent"}`` over the vocabulary.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator, List

import numpy as np


@dataclasses.dataclass
class Arrival:
    due_s: float                 # seconds after the window opens
    prompt: np.ndarray           # int32 token ids
    max_new_tokens: int


def draw_lengths(spec: Dict[str, Any], rng: np.random.Generator,
                 n: int) -> np.ndarray:
    """``n`` lengths: the law's quantiles at the midpoints of ``n`` equal
    strata, in a seeded order."""
    from scipy.special import ndtri

    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length law {spec['dist']!r}")
    u = (rng.permutation(n) + 0.5) / max(n, 1)
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * ndtri(u))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def arrival_times(arrivals: Dict[str, Any], rate_rps: float, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Exactly ``round(rate_rps * seconds)`` due times in ``[0, seconds)``:
    a Poisson process given its count is that many independent uniform
    points."""
    if arrivals["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    return np.sort(rng.random(int(round(rate_rps * seconds))) * seconds)


def serve_schedule(mix: Dict[str, Any], rate_rps: float, seconds: float,
                   seed: int, vocab: int) -> List[Arrival]:
    """The requests due in ``[0, seconds)``, in order of their due time."""
    rng = np.random.default_rng([int(mix["pattern_seed"]), 0x5E21E])
    content = np.random.default_rng([int(seed), 0xC0])
    times = arrival_times(mix["arrivals"], rate_rps, seconds, rng)
    # discarded: the generator this pattern was measured with drew one
    # number a request here, and the pattern stays as it was measured
    rng.random(len(times))
    n_prompt = draw_lengths(mix["prompt_tokens"], rng, len(times))
    n_out = draw_lengths(mix["output_tokens"], rng, len(times))
    cap = int(mix["max_total_tokens"])
    out_min = int(mix["output_tokens"]["min"])
    out = []
    for t, n_p, n_o in zip(times, n_prompt, n_out):
        prompt = content.integers(0, vocab, int(n_p),
                                  dtype=np.int32)[: cap - out_min]
        out.append(Arrival(float(t), prompt,
                           max(1, min(int(n_o), cap - len(prompt)))))
    return out


def token_law(spec: Dict[str, Any], vocab: int) -> np.ndarray:
    """Cumulative distribution over token ids 0..vocab-1."""
    if spec["dist"] != "zipf":
        raise ValueError(f"unknown token law {spec['dist']!r}")
    w = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** float(
        spec["exponent"])
    return np.cumsum(w / w.sum())


def train_batches(mix: Dict[str, Any], seed: int, vocab: int,
                  rows: int) -> Iterator[np.ndarray]:
    """An endless stream of fresh ``[rows, seq_len]`` int32 batches: rows
    drawn i.i.d. from the mix's token law (documents concatenated without
    boundary masks are, to the model, one stream)."""
    rng = np.random.default_rng([int(seed), 0x7A11])
    cdf = token_law(mix["tokens"], vocab)
    seq = int(mix["seq_len"])
    while True:
        u = rng.random((rows, seq))
        yield np.minimum(np.searchsorted(cdf, u), vocab - 1).astype(np.int32)
