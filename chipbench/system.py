"""The system under test: the HNTL ``VectorStore``, driven through its
public calls, as ``ServeEngine.retrieve`` drives it."""
from __future__ import annotations

import time

import numpy as np

# ``VectorStore.search`` arguments a traffic mix may set for every call.
# ``ts_range`` filters, and the reference filters the same way; the others
# change how the store searches, not what a right answer is.  ``mesh`` is
# given as the number of grain shards and built with ``make_search_mesh``.
SEARCH_ARGS = {"ts_range", "nprobe", "pool", "budgets", "scan_impl",
               "route_mode", "adaptive", "probe_margin", "min_probes",
               "mesh", "shard_queries"}


def search_kwargs(mix_search: dict) -> dict:
    """A mix's ``search`` parameters as ``VectorStore.search`` takes them."""
    unknown = set(mix_search) - SEARCH_ARGS
    if unknown:
        raise ValueError(f"search arguments {sorted(unknown)} are not "
                         f"passed on; a mix may set {sorted(SEARCH_ARGS)}")
    kw = dict(mix_search)
    for key in ("ts_range", "budgets"):
        if key in kw:
            kw[key] = tuple(kw[key])
    if "mesh" in kw:
        from repro.launch.mesh import make_search_mesh
        kw["mesh"] = make_search_mesh(int(kw["mesh"]))
    return kw


class StoreSystem:
    """Builds the store from the corpus (``add`` in ``seal_threshold``
    chunks, then ``seal``) and answers ``search(q) -> (ids, dists)`` with
    both on the host, every call with the mix's ``search`` arguments."""

    def __init__(self, cfg: dict, x: np.ndarray, ts: np.ndarray,
                 cold_dir: str, search: dict = None):
        from repro.core import HNTLConfig
        from repro.core.store import VectorStore
        h = cfg["hntl"]
        hcfg = HNTLConfig(d=cfg["d"], k=h["k"], s=h["s"], pool=h["pool"],
                          n_grains=h["n_grains"], nprobe=h["nprobe"])
        self.topk, self.mode = cfg["topk"], h["mode"]
        self.kw = search_kwargs(search or {})
        seal = cfg["seal_threshold"]
        self.store = VectorStore(hcfg, seal_threshold=seal, cold_dir=cold_dir)
        self.seal_s = []        # seconds each add that sealed a segment took
        for lo in range(0, x.shape[0], seal):
            t = time.perf_counter()
            self.store.add(x[lo:lo + seal], ts=ts[lo:lo + seal])
            self.seal_s.append(time.perf_counter() - t)
        self.store.seal()

    def search(self, q: np.ndarray, **override):
        res = self.store.search(q, topk=self.topk, mode=self.mode,
                                **{**self.kw, **override})
        return np.asarray(res.ids, np.int64), np.asarray(res.dists)

    def routing_plane(self):
        """(centroids [G, d], sizes [G], cap) of the sealed segments, in the
        order the stacked plane routes over them, read through the
        store's public snapshot."""
        segs = self.store.snapshot().segments
        cents = np.concatenate([np.asarray(s.index.routing.centroids)
                                for s in segs])
        sizes = np.concatenate([np.asarray(s.index.routing.sizes)
                                for s in segs])
        cap = max(int(s.index.grains.cap) for s in segs)
        return cents, sizes, cap

    def close(self) -> None:
        del self.store
