"""SyntheticLM draws the same tokens as the ``Generator.choice`` loop it
replaced: fresh tokens come from a CDF built once, looked up with the same
``rng.random`` uniforms ``choice`` draws, in the same order."""

import itertools

import numpy as np
import pytest

from repro.data.pipeline import DataConfig, SyntheticLM


def _choice_batch(cfg: DataConfig, step: int) -> dict[str, np.ndarray]:
    """The generator as it was: ``rng.choice(..., p=p)`` at every position."""
    ranks = np.arange(1, cfg.vocab_size + 1, dtype=np.float64)
    p = ranks ** (-cfg.theta)
    p = p / p.sum()
    rng = np.random.default_rng((cfg.seed << 20) ^ step)
    b, s = cfg.global_batch, cfg.seq_len
    toks = np.empty((b, s + 1), dtype=np.int32)
    toks[:, 0] = rng.choice(cfg.vocab_size, size=b, p=p)
    for t in range(1, s + 1):
        copy = rng.random(b) < cfg.copy_prob
        back = rng.integers(1, min(t, cfg.window) + 1, size=b)
        copied = toks[np.arange(b), t - back]
        fresh = rng.choice(cfg.vocab_size, size=b, p=p)
        toks[:, t] = np.where(copy & (t > 1), copied, fresh)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def _assert_same(cfg: DataConfig, step: int) -> dict[str, np.ndarray]:
    got = SyntheticLM(cfg).batch(step)
    want = _choice_batch(cfg, step)
    for k in ("tokens", "labels"):
        assert got[k].dtype == np.int32
        np.testing.assert_array_equal(got[k], want[k], err_msg=f"{k} {cfg} step {step}")
    return got


# Each shape case runs every (window, copy_prob, theta, seed) combination.
_STREAM = list(itertools.product((1, 8), (0.0, 0.6, 1.0), (0.0, 1.1),
                                 (0, 5, 2147484021)))


@pytest.mark.parametrize("global_batch", [1, 5])
@pytest.mark.parametrize("seq_len", [1, 2, 9, 64])
@pytest.mark.parametrize("vocab_size", [2, 100, 4097])
def test_batch_matches_choice_loop(vocab_size, seq_len, global_batch):
    for step, (window, copy_prob, theta, seed) in enumerate(_STREAM):
        cfg = DataConfig(vocab_size=vocab_size, seq_len=seq_len,
                         global_batch=global_batch, seed=seed, theta=theta,
                         copy_prob=copy_prob, window=window)
        _assert_same(cfg, step)


def test_batch_matches_choice_loop_at_benchmark_shape():
    # granite-L4.train-4k: vocab 49,155, seq 4096, batch 5, Zipf 1.1.
    cfg = DataConfig(vocab_size=49155, seq_len=4096, global_batch=5, seed=1003)
    got = _assert_same(cfg, 0)
    np.testing.assert_array_equal(got["tokens"][:, 1:], got["labels"][:, :-1])


class _NoChoice(np.random.Generator):
    def choice(self, *args, **kwargs):
        raise AssertionError("Generator.choice is O(vocab) per call")


def test_batch_makes_no_choice_call(monkeypatch):
    cfg = DataConfig(vocab_size=4097, seq_len=64, global_batch=5, seed=5)
    want = SyntheticLM(cfg).batch(3)
    monkeypatch.setattr(np.random, "default_rng",
                        lambda seed: _NoChoice(np.random.PCG64(seed)))
    got = SyntheticLM(cfg).batch(3)
    np.testing.assert_array_equal(got["tokens"], want["tokens"])
    np.testing.assert_array_equal(got["labels"], want["labels"])
