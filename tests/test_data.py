"""Data pipeline tests: determinism and prefetch."""
import numpy as np
import pytest

from repro.train.data import Prefetcher, TokenStream


def test_token_stream_deterministic():
    s1 = TokenStream(vocab_size=100, batch=4, seq=16, seed=7)
    s2 = TokenStream(vocab_size=100, batch=4, seq=16, seed=7)
    b1, b2 = s1.batch_at(3), s2.batch_at(3)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    assert not np.array_equal(np.asarray(s1.batch_at(4)["tokens"]),
                              np.asarray(b1["tokens"]))


def test_token_stream_labels_shifted():
    s = TokenStream(vocab_size=50, batch=2, seq=8, seed=0)
    b = s.batch_at(0)
    np.testing.assert_array_equal(np.asarray(b["labels"][:, :-1]),
                                  np.asarray(b["tokens"][:, 1:]))


def test_token_stream_learnable_signal():
    """With signal=1.0 the stream is a pure deterministic bigram chain."""
    s = TokenStream(vocab_size=32, batch=2, seq=32, seed=1, signal=1.0)
    b = np.asarray(s.batch_at(0)["tokens"])
    for t in range(1, 32):
        np.testing.assert_array_equal(b[:, t], s.table[b[:, t - 1]])


def test_prefetcher_preserves_order():
    it = iter([{"x": np.array([i])} for i in range(10)])
    got = [int(b["x"][0]) for b in Prefetcher(it, depth=3)]
    assert got == list(range(10))
