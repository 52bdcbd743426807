"""The synthetic token source: a sparse Markov chain at any vocabulary."""

import numpy as np

from repro.data import tokens as tok


def test_markov_table_is_linear_in_vocab_at_published_size():
    fav, cdf = tok.make_markov_table(151_936, seed=0)  # qwen3's vocabulary
    assert fav.shape == cdf.shape == (151_936, 4)
    assert np.all(np.diff(cdf, axis=1) >= 0) and np.allclose(cdf[:, -1], 0.5)


def test_shard_batch_is_a_pure_function_of_shard_and_step():
    table = tok.make_markov_table(1000, seed=3)
    a = tok.shard_batch(table, 5, 7, 2, 64)
    np.testing.assert_array_equal(a, tok.shard_batch(table, 5, 7, 2, 64))
    assert a.shape == (2, 64) and a.dtype == np.int32
    assert a.min() >= 0 and a.max() < 1000
    assert not np.array_equal(a, tok.shard_batch(table, 6, 7, 2, 64))


def test_favoured_successors_carry_their_mass():
    fav, cdf = table = tok.make_markov_table(500, seed=1)
    toks = tok.markov_tokens(table, 64, 256, seed=2)
    prev, nxt = toks[:, :-1].ravel(), toks[:, 1:].ravel()
    hit = (fav[prev] == nxt[:, None]).any(axis=1).mean()
    # Half the mass goes to the 4 favourites; uniform draws add ~4/500.
    assert 0.45 < hit < 0.56
