"""Request streams drawn from a traffic mix and a seed.

Every seed gets the same set of requests in another order, so that seeds
change the order of the work and not its amount: an open loop's ``N =
rate x seconds`` inter-arrival gaps are the ``N`` quantiles of the
exponential law at that rate (their mean is ``1 / rate``), the classes are
the shares' counts, and the images are the pool's, each in an order drawn
from the seed.  (``repro.runtime.traffic`` draws i.i.d. exponential gaps and
labels instead, whose count and mean change with the seed.)  Each stream has
its own generator, seeded by ``(seed, stream)``, and any whole number, above
32 bits too, is a seed.
"""
from __future__ import annotations

import numpy as np

ARRIVALS, LABELS, IMAGES = 1, 2, 3


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**64, stream])


def poisson_times(g: np.random.Generator, rate_hz: float, horizon_s: float) -> np.ndarray:
    """Arrival times in ``[0, horizon_s)``: the exponential law's quantiles
    at ``(i + 1/2) / N`` as gaps, ``N = round(rate_hz * horizon_s)``, shuffled."""
    if rate_hz <= 0:
        raise ValueError(f"rate_hz must be positive, got {rate_hz}")
    n = int(round(rate_hz * horizon_s))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate_hz
    times = np.cumsum(g.permutation(gaps))
    return times[times < horizon_s]


def spread(g: np.random.Generator, counts: np.ndarray, n: int) -> np.ndarray:
    """``n`` items, ``counts[i]`` of kind ``i`` per ``counts.sum()``, in an
    order drawn from ``g``."""
    reps = -(-n // int(counts.sum()))
    return g.permutation(np.repeat(np.arange(len(counts)), counts * reps))[:n]


def labels(g: np.random.Generator, classes: list[dict], n: int) -> np.ndarray:
    """One class index per request, in the classes' shares (per 1000)."""
    shares = np.array([c["share"] for c in classes], dtype=np.float64)
    return spread(g, np.round(1000 * shares / shares.sum()).astype(int), n)


def image_order(g: np.random.Generator, pool: int, n: int) -> np.ndarray:
    """Pool image of each of ``n`` requests, every image equally often."""
    return spread(g, np.ones(pool, dtype=int), n)


class Stream:
    """The requests of one run: image, class and (open loop) due time of the
    ``k``-th request, all fixed by the seed."""

    def __init__(self, mix: dict, seed: int, seconds: float, cap: int):
        self.classes = mix["classes"]
        if mix["loop"] == "open":
            self.due = poisson_times(rng(seed, ARRIVALS), float(mix["rate_hz"]), seconds)
            cap = len(self.due)
        elif mix["loop"] == "closed":
            self.due = None
        else:
            raise ValueError(f"unknown loop {mix['loop']!r}")
        self.cls = labels(rng(seed, LABELS), self.classes, cap)
        self.image = image_order(rng(seed, IMAGES), int(mix["pool"]), cap)
