"""Draws from the seed that the drivers share: arrivals and samples."""
from __future__ import annotations

import numpy as np

__all__ = ["Reservoir", "arrivals"]


def arrivals(spec: dict, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Due times (s from the window's start) of an open-loop schedule.

    ``{"kind": "poisson", "rate_per_s": r}``: ``round(r * seconds)`` requests
    whose gaps are the exponential distribution's quantiles at
    ``(i + 1/2) / n``, scaled to span ``seconds`` and shuffled by ``rng``.
    Every seed thus sends the same requests at the same mean rate with the
    same set of gaps, in another order: the seed does not change the work.
    """
    if spec["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {spec['kind']!r}")
    n = int(round(spec["rate_per_s"] * seconds))
    if n < 1:
        raise ValueError(f"{spec} sends no request in {seconds} s")
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    rng.shuffle(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


class Reservoir:
    """A uniform sample of ``size`` items from a stream of unknown length,
    drawn by ``rng`` (Algorithm R): the same seed and stream length keep
    the same items.  Items are kept by reference (device arrays stay on
    the device, unsynchronised)."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size = size
        self.rng = rng
        self.items: list = []
        self.seen = 0

    def offer(self, item) -> None:
        if len(self.items) < self.size:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.size:
                self.items[j] = item
        self.seen += 1
