"""The comparisons that decide ``correct``."""

from __future__ import annotations

import numpy as np

# How far below the reference forward's best logit a served token may sit,
# in steps of bf16 at that logit (8 significant bits: one step is 2^-7 of
# the logit's power of two).  The server computes in bf16 and prefills in
# chunks, the reference in float32 in one piece; seeded random weights
# leave the best two of ~50k logits close, so the served argmax may be the
# reference's runner-up by a rounding.  On the chip PR 23 saw at most 1.0
# step; a token read from a wrong page or a stale cache row sits hundreds
# of steps below.  The bound and the measure are chip_smoke.py's.
SERVE_TIE_STEPS = 4.0

# Relative difference allowed between the engine's loss (bf16 compute on
# fp32 master weights) and the reference's (float32, highest precision) on
# the same weights and tokens.  One bf16 rounding is 2^-8 = 0.4%, but the
# loss is a mean over two thousand tokens of a log-sum-exp of
# fp32-accumulated logits, so roundings average rather than add: seven runs
# on the v5e differed by 1.6e-6 to 4.7e-5 (PR 24; on trained weights, where
# logits are larger than at the start).  1e-3 leaves those twenty times
# their worst.  What it must catch is small at the initial weights, where
# every logit is near 0 and the loss near ln V: the reference's own loss at
# GPT-2 XL's widths (4 layers, 256 tokens) moves by 2.5e-3 without the
# causal mask and by 1.6e-3 without the position embedding.
TRAIN_LOSS_RTOL = 1e-3


def reference_loss(ref, params, config, batch, device) -> float:
    """Mean next-token cross-entropy of ``batch`` [B, S] under the plain
    reference ``ref`` (its ``logits_rows``), one row at a time."""
    from scipy.special import logsumexp

    total, count = 0.0, 0
    for row in batch:
        n = len(row) - 1
        logits = np.asarray(ref.logits_rows(params, config, row,
                                            list(range(n)), device),
                            np.float64)
        total += float((logsumexp(logits, axis=-1)
                        - logits[np.arange(n), np.asarray(row[1:])]).sum())
        count += n
    return total / count


def bf16_steps_below_best(rows: np.ndarray, tokens) -> np.ndarray:
    """For each row of reference logits ``rows`` [n, V] and the token
    served at that position: how many bf16 steps below the row's best
    logit the token's logit sits."""
    rows = np.asarray(rows, np.float32)
    tokens = np.asarray(tokens)
    best = rows.max(axis=-1)
    got = rows[np.arange(len(tokens)), tokens]
    step = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(best), 1e-30))) - 7)
    return (best - got) / step
