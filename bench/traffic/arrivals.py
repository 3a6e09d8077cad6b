"""Open-loop arrival schedules, a copy of the arithmetic of
``repro.serve.loop.poisson_arrivals`` / ``burst_arrivals`` with one
change: the inter-arrival gaps are the mid-quantiles of the exponential
distribution, the same multiset for every seed, and the seed draws only
their order.  A window of ``seconds`` then holds the same number of
requests in every run, and seeds differ in when they come, not in how
many.
"""
from __future__ import annotations

import numpy as np


def _exp_gaps(n: int, rate_hz: float, rng: np.random.Generator
              ) -> np.ndarray:
    if rate_hz <= 0 or n < 1:
        raise ValueError("rate_hz and the request count must be > 0")
    q = (np.arange(n) + 0.5) / n
    return rng.permutation(-np.log1p(-q) / rate_hz)


def poisson(rate_hz: float, seconds: float, rng: np.random.Generator
            ) -> np.ndarray:
    """Due offsets (s) of ``round(rate_hz * seconds)`` Poisson arrivals."""
    n = max(1, int(round(rate_hz * seconds)))
    return np.cumsum(_exp_gaps(n, rate_hz, rng))


def burst(rate_hz: float, seconds: float, rng: np.random.Generator, *,
          on_s: float, off_s: float) -> np.ndarray:
    """ON/OFF-modulated Poisson: ``rate_hz`` for ``on_s``, silence for
    ``off_s``; the mean rate is ``rate_hz * on_s / (on_s + off_s)``."""
    duty = on_s / (on_s + off_s)
    t_on = poisson(rate_hz, seconds * duty, rng)
    return np.floor(t_on / on_s) * (on_s + off_s) + np.mod(t_on, on_s)


def schedule(mix: dict, seconds: float, rng: np.random.Generator
             ) -> np.ndarray:
    """The due offsets a traffic file's ``arrivals`` block asks for."""
    a = mix["arrivals"]
    if a["kind"] == "poisson":
        return poisson(a["rate_hz"], seconds, rng)
    if a["kind"] == "burst":
        return burst(a["rate_hz"], seconds, rng, on_s=a["on_s"],
                     off_s=a["off_s"])
    raise ValueError(f"no open-loop schedule for arrivals {a['kind']!r}")
