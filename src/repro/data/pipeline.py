"""Deterministic synthetic token pipeline.

Produces reproducible LM batches (Zipfian unigram mixture with in-context
structure so the loss has learnable signal), shardable across hosts: batch
``i`` is a pure function of (seed, step), so any host can regenerate any
shard after a restart — the data-plane half of fault tolerance.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["DataConfig", "SyntheticLM", "make_batch"]


@dataclasses.dataclass(frozen=True)
class DataConfig:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    theta: float = 1.1          # unigram Zipf exponent
    copy_prob: float = 0.6      # P(next token copies a recent token)
    window: int = 8


class SyntheticLM:
    """Markov-ish synthetic LM stream: next token either copies a recent
    token (learnable structure) or draws from a Zipfian unigram.

    A fresh token is drawn by inverse-CDF sampling: one uniform from
    ``rng.random`` looked up in the unigram's CDF, which is built once
    here.  That is the draw ``Generator.choice(vocab, p=p)`` makes, with
    the CDF computed by the same float64 operations, so the stream is
    consumed in the same order and the tokens are those ``choice`` gives,
    without its O(vocab) work per call.  A batch is still a pure function
    of (seed, step)."""

    def __init__(self, cfg: DataConfig):
        self.cfg = cfg
        ranks = np.arange(1, cfg.vocab_size + 1, dtype=np.float64)
        p = ranks ** (-cfg.theta)
        self._p = p / p.sum()
        cdf = self._p.cumsum()
        cdf /= cdf[-1]
        self._cdf = cdf

    def batch(self, step: int) -> dict[str, np.ndarray]:
        cfg = self.cfg
        rng = np.random.default_rng((cfg.seed << 20) ^ step)
        b, s = cfg.global_batch, cfg.seq_len
        rows = np.arange(b)
        toks = np.empty((b, s + 1), dtype=np.int32)
        toks[:, 0] = self._cdf.searchsorted(rng.random(b), side="right")
        for t in range(1, s + 1):
            copy = rng.random(b) < cfg.copy_prob
            back = rng.integers(1, min(t, cfg.window) + 1, size=b)
            copied = toks[rows, t - back]
            fresh = self._cdf.searchsorted(rng.random(b), side="right")
            toks[:, t] = np.where(copy & (t > 1), copied, fresh)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def __iter__(self) -> Iterator[dict[str, np.ndarray]]:
        step = 0
        while True:
            yield self.batch(step)
            step += 1


def make_batch(cfg: DataConfig, step: int, *, device_put=True, sharding=None):
    arrs = SyntheticLM(cfg).batch(step)
    out = {k: jnp.asarray(v) for k, v in arrs.items()}
    if device_put and sharding is not None:
        out = {k: jax.device_put(v, sharding[k]) for k, v in out.items()}
    return out
