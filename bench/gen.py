"""Traffic generation shared by the drivers: everything a window's inputs
are drawn from, made from the run's seed alone."""

from __future__ import annotations

import numpy as np

# The driver's seeds reach past 32 bits; every stream is derived from them
# through numpy's SeedSequence, never passed to a 32-bit key directly.
SEED_SPACE = 2**31 - 1


def rng(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator for one named use of the run's seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def sub_seeds(seed: int, stream: int, count: int) -> np.ndarray:
    """``count`` seeds that fit a 32-bit key, for the program's own calls."""
    return rng(seed, stream).integers(0, SEED_SPACE, size=count)


def deadline_masks(
    seed: int,
    num_nodes: int,
    rounds: int,
    *,
    deadline: float = 2.0,
    sigma: float = 0.25,
    p_spike: float = 0.08,
    spike_scale: float = 4.0,
    persistence: float = 0.5,
) -> np.ndarray:
    """(rounds, num_nodes) alive masks of the deadline straggler model.

    Each worker's round latency is lognormal(0, sigma); with probability
    ``p_spike`` a worker is slowed ``spike_scale``-fold, and a slowdown
    persists into the next round with probability ``persistence``.  A worker
    whose latency passes ``deadline`` straggles.  (The same model as the
    program's ``DeadlineStragglerSimulator``, kept here so that the traffic
    does not change when the program does.)
    """
    r = rng(seed, 0xDEAD)
    spiked = np.zeros(num_nodes, dtype=bool)
    masks = np.zeros((rounds, num_nodes), dtype=bool)
    for t in range(rounds):
        fresh = r.random(num_nodes) < p_spike
        stay = spiked & (r.random(num_nodes) < persistence)
        spiked = fresh | stay
        lat = r.lognormal(mean=0.0, sigma=sigma, size=num_nodes)
        lat = np.where(spiked, lat * spike_scale, lat)
        masks[t] = lat <= deadline
    return masks


def poisson_arrivals(seed: int, rate: float, seconds: float, stream: int = 0xA11) -> np.ndarray:
    """Open-loop arrival times in [0, seconds) at ``rate`` per second."""
    r = rng(seed, stream)
    n = int(rate * seconds * 1.5) + 64
    t = np.cumsum(r.exponential(1.0 / rate, size=n))
    while t[-1] < seconds:
        t = np.concatenate([t, t[-1] + np.cumsum(r.exponential(1.0 / rate, size=n))])
    return t[t < seconds]
