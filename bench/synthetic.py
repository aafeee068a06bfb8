"""The benchmark's own copy of the trainer's synthetic token stream.

A yardstick copy of ``repro.data.pipeline.SyntheticLM``: batch ``step`` of
``seed`` is a pure function of the two, so the check can regenerate any
batch the trainer consumed and compare it token by token.  The program's
generator may be rewritten; this one is not.
"""

from __future__ import annotations

import numpy as np


def zipf_probs(vocab_size: int, theta: float) -> np.ndarray:
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    p = ranks ** (-theta)
    return p / p.sum()


def batch(step: int, *, seed: int, vocab_size: int, seq_len: int,
          global_batch: int, theta: float, copy_prob: float, window: int,
          probs: np.ndarray | None = None) -> dict[str, np.ndarray]:
    """Tokens and next-token labels, each (global_batch, seq_len) int32.

    Each next token copies one of the last ``window`` tokens with
    probability ``copy_prob``, else is drawn from a Zipf(``theta``)
    unigram over the vocabulary."""
    p = zipf_probs(vocab_size, theta) if probs is None else probs
    rng = np.random.default_rng((seed << 20) ^ step)
    b, s = global_batch, seq_len
    toks = np.empty((b, s + 1), dtype=np.int32)
    toks[:, 0] = rng.choice(vocab_size, size=b, p=p)
    for t in range(1, s + 1):
        copy = rng.random(b) < copy_prob
        back = rng.integers(1, min(t, window) + 1, size=b)
        copied = toks[np.arange(b), t - back]
        fresh = rng.choice(vocab_size, size=b, p=p)
        toks[:, t] = np.where(copy & (t > 1), copied, fresh)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
