"""Synthetic token pipelines for LM training/serving tests and examples.

Deterministic per-shard streams (seeded by shard id + step) so that the
redundant pipeline's invariant — every replica of a shard sees *identical*
data — holds across groups and across restarts by construction.
"""

from __future__ import annotations

import numpy as np

__all__ = ["shard_batch", "markov_tokens", "make_markov_table"]


def make_markov_table(vocab: int, *, seed: int = 0, fav_mass: float = 0.5):
    """A sparse Markov chain — gives the LM something learnable so loss
    curves in tests/examples actually descend.

    Each token prefers 4 successors, which share ``fav_mass`` of its
    transition probability; the rest is uniform over the vocabulary.  Kept
    as the (V, 4) successor table and the cumulative split, so its size is
    linear in V: a dense V×V table at a published vocabulary (151,936 for
    qwen3) would not fit in host memory.
    """
    rng = np.random.default_rng(seed)
    fav = rng.integers(0, vocab, size=(vocab, 4))
    split = rng.dirichlet(np.ones(4), size=vocab)
    return fav, np.cumsum(split, axis=1) * fav_mass


def markov_tokens(table, n: int, T: int, *, seed: int) -> np.ndarray:
    fav, cdf = table
    rng = np.random.default_rng(seed)
    V = fav.shape[0]
    out = np.empty((n, T), dtype=np.int32)
    cur = rng.integers(0, V, size=n)
    out[:, 0] = cur
    for t in range(1, T):
        u = rng.random(n)
        c = cdf[cur]
        j = (u[:, None] < c).argmax(axis=1)
        uniform = rng.integers(0, V, size=n)
        cur = np.where(u < c[:, -1], fav[cur, j], uniform)
        out[:, t] = cur
    return out


def shard_batch(table, shard_id: int, step: int, mb: int, T: int) -> np.ndarray:
    """The microbatch of shard ``shard_id`` at ``step`` — a pure function of
    (shard, step), which is what makes redundant replicas consistent."""
    return markov_tokens(table, mb, T, seed=(shard_id * 1_000_003 + step) & 0x7FFFFFFF)
