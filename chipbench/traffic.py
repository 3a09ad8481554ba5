"""The one traffic generator: every mix is a data file of parameters.

  batch     queries per search call; with ``rate_qps``, the most that one
            call carries
  rate_qps  absent: a closed loop, one caller that sends its next call when
            the last one's answer is back.  Present: an open loop, requests
            arriving at this fixed rate, evenly spaced, served by one caller
            that takes every request waiting (up to ``batch``) in each call
  search    keyword arguments of ``VectorStore.search`` for every call
            (``chipbench/system.py`` lists those it passes on)
  about     what the mix stands for, read by people

The queries are the held-out set in a seeded order, cycled.  Every seed
gets the same batch sizes, arrivals and set of queries; the seed changes
only their order.
"""
from __future__ import annotations

import numpy as np

from .data import host_rng

_ORDER_STREAM = 4
KEYS = {"batch", "rate_qps", "search", "about"}


class Generator:
    def __init__(self, mix: dict, n_queries: int, seed: int):
        unknown = set(mix) - KEYS
        if unknown:
            raise ValueError(f"unknown traffic keys {sorted(unknown)}; "
                             f"a mix has {sorted(KEYS)}")
        self.batch = int(mix["batch"])
        if not 1 <= self.batch <= n_queries:
            raise ValueError(f"batch {self.batch} outside 1..{n_queries}")
        rate = mix.get("rate_qps")
        if rate is not None and not float(rate) > 0:
            raise ValueError(f"rate_qps {rate!r} is not above 0")
        self.rate = None if rate is None else float(rate)
        self.search = dict(mix.get("search", {}))
        self.order = host_rng(seed, _ORDER_STREAM).permutation(n_queries)
        self.pos = 0

    @property
    def open_loop(self) -> bool:
        return self.rate is not None

    def arrival(self, i: int) -> float:
        """Seconds from the window's start to request ``i``'s arrival."""
        return i / self.rate

    def warmup(self) -> np.ndarray:
        """A batch of the window's shape, not counted in any metric."""
        return self.order[:self.batch]

    def next(self, n: int = 0) -> np.ndarray:
        """Query indices of the next call: ``n`` of them, or ``batch``."""
        n_q = self.order.shape[0]
        n = n or self.batch
        idx = self.order[np.arange(self.pos, self.pos + n) % n_q]
        self.pos = (self.pos + n) % n_q
        return idx
