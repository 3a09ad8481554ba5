#!/usr/bin/env python3
"""Smoke run of the HNTL vector store's search path on TPU chips.

  python3 chip_smoke.py              # one chip: the store's main path
  python3 chip_smoke.py --chips 4    # the grain-sharded plane on 4 chips

One chip.  1,048,576 seeded 768-d vectors (the shape of VectorDBBench's
768-d "1M" performance case, generated by ``anisotropic_manifold`` rather
than downloaded) go through the calls ``ServeEngine.retrieve`` makes:
``VectorStore.add`` in ``seal_threshold`` chunks (8 sealed segments, one
fused dispatch per search) and ``VectorStore.search`` with the paper's
configuration (the ``HNTLConfig`` defaults: k=32, s=8, pool C=20).  Phases:
a few Mode B batches on the default scan plane, one tag-filtered batch,
one batch after ``delete()`` of a slice of ids.  Each phase also runs on
the jnp "ref" plane, and Recall@10 of both is measured against
``flat.flat_search`` on the chip.  It fails unless the default plane
resolved to "fused", its top-10 ids match "ref" on >= 99% of queries,
its Recall@10 is >= 0.90 and within 0.005 of "ref"'s, and no deleted id
comes back.

Four chips (``--chips 4``).  Only the grain-sharded plane
(``search(mesh=make_search_mesh(4))``) and what it is compared with, the
single-device fused plane in the same process, on the same corpus: sharded
Recall@10 must be no lower than single-device minus 0.005 (nprobe and pool
are per shard), a small store under exhaustive knobs must give
bit-identical ids, and every chip must hold its share of the plane.

Earlier output lines give sizes, times, recall per phase and device
memory.  The last line is one JSON object: ok and the device as JAX
reports it.  With no TPU the script exits nonzero before any phase.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

N_VECTORS = 1 << 20          # VectorDBBench 768-d "1M" case
SEAL_ROWS = 1 << 17          # 8 sealed segments
N_GRAINS = 64                # per segment: 512 grains of ~2048 vectors
NPROBE = 192                 # of 512: recall here is bound by probing
BATCH = 64                   # queries per search call
N_BATCHES = 4
REF_CHUNK = 16               # queries per "ref" call: its probed-panel
                             # gather is O(queries * nprobe * cap * k)
TAG_BITS = 8                 # one tag per vector; a filter keeps 1/8
DELETE_SLICE = (0, 1 << 16)  # ids deleted before the last phase
TOPK = 10
MIN_RECALL = 0.90
RECALL_SLACK = 0.005
MIN_AGREEMENT = 0.99


def log(msg: str) -> None:
    print(msg, flush=True)


def tpu_devices(chips: int):
    """The TPU devices, or exit nonzero before any phase runs."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"chip_smoke: JAX found no devices: {e}")
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: needs {chips} TPU chips, JAX found "
                         f"{len(devices)}")
    return devices


def make_corpus(n: int, d: int, seed: int):
    from repro.data import synthetic as syn
    x = syn.anisotropic_manifold(n, d, seed=seed)
    rng = np.random.default_rng(seed + 1)
    tags = (np.uint32(1) << rng.integers(0, TAG_BITS, n).astype(np.uint32))
    return x, tags


def query_batches(x: np.ndarray, n_batches: int, batch: int, seed: int):
    from repro.data import synthetic as syn
    return [syn.queries_from(x, batch, seed=seed + 10 + i)
            for i in range(n_batches)]


def build_store(cfg, x, tags, seal_rows: int):
    from repro.core.store import VectorStore
    st = VectorStore(cfg, seal_threshold=seal_rows)
    t0 = time.perf_counter()
    for lo in range(0, x.shape[0], seal_rows):
        st.add(x[lo:lo + seal_rows], tags=tags[lo:lo + seal_rows])
    st.seal()
    return st, time.perf_counter() - t0


class Truth:
    """Exact top-10 from ``flat.flat_search`` on the device, with the
    corpus resident only while the answers are computed."""

    def __init__(self, x: np.ndarray):
        import jax
        self.xd = jax.device_put(x)

    def __call__(self, q: np.ndarray, valid=None) -> np.ndarray:
        import jax
        import jax.numpy as jnp
        from repro.core.flat import flat_search
        v = None if valid is None else jax.device_put(valid)
        return np.asarray(flat_search(self.xd, jnp.asarray(q), topk=TOPK,
                                      valid=v).ids, np.int64)

    def release(self) -> None:
        self.xd.delete()


def recall(ids, truth) -> float:
    from repro.core.flat import recall_at_k
    return float(recall_at_k(np.asarray(ids, np.int64), truth))


def same_sets(a, b) -> list:
    """Per query: do the two top-10 id lists hold the same ids?"""
    return [set(x.tolist()) == set(y.tolist()) for x, y in zip(a, b)]


def timed_search(st, q, **kw):
    t0 = time.perf_counter()
    res = st.search(q, topk=TOPK, mode="B", **kw)
    ids = np.asarray(res.ids, np.int64)
    return ids, time.perf_counter() - t0


def timed_ref_search(st, q, **kw):
    t0 = time.perf_counter()
    ids = np.concatenate([timed_search(st, q[i:i + REF_CHUNK],
                                       scan_impl="ref", **kw)[0]
                          for i in range(0, q.shape[0], REF_CHUNK)])
    return ids, time.perf_counter() - t0


def log_first_calls(st, q, **kw) -> None:
    """First call of each plane (stack + compile + search) vs a warm one."""
    for name, fn in (("default", timed_search), ("ref", timed_ref_search)):
        _, first = fn(st, q, **kw)
        _, warm = fn(st, q, **kw)
        log(f"{name} plane: first call (stack + compile + search) "
            f"{first:.1f}s, warm call {warm:.3f}s")


def log_memory(devices) -> None:
    log("device memory:")
    for d in devices:
        ms = d.memory_stats() or {}
        log(f"  {d}: bytes_in_use={ms.get('bytes_in_use')} "
            f"peak_bytes_in_use={ms.get('peak_bytes_in_use')} "
            f"bytes_limit={ms.get('bytes_limit')}")


class Checks:
    def __init__(self):
        self.failed = []

    def expect(self, ok: bool, what: str) -> None:
        log(f"check {'ok  ' if ok else 'FAIL'} {what}")
        if not ok:
            self.failed.append(what)


def smoke_one_chip(devices, *, n: int, seal_rows: int, n_grains: int,
                   nprobe: int, batch: int, n_batches: int,
                   delete_slice: tuple, seed: int) -> Checks:
    from repro.core import HNTLConfig
    from repro.core.scanplane import get_scan_plane

    checks = Checks()
    cfg = HNTLConfig(n_grains=n_grains, nprobe=nprobe)
    t0 = time.perf_counter()
    x, tags = make_corpus(n, cfg.d, seed)
    batches = query_batches(x, n_batches, batch, seed)
    lo, hi = delete_slice
    rng = np.random.default_rng(seed + 2)
    # the delete phase queries sit next to rows it deletes, so each one's
    # nearest neighbour is gone
    q_del = x[rng.choice(np.arange(lo, hi), batch, replace=False)] \
        + np.float32(0.01) * rng.standard_normal((batch, cfg.d),
                                                 dtype=np.float32)
    q_tag = query_batches(x, 1, batch, seed + 100)[0]
    tag_mask = 1
    log(f"corpus: N={n} d={cfg.d} k={cfg.k} s={cfg.s} pool={cfg.pool} "
        f"n_grains/segment={n_grains} nprobe={nprobe} "
        f"seal_threshold={seal_rows} batch={batch} "
        f"({time.perf_counter() - t0:.1f}s to generate)")

    truth = Truth(x)
    alive = np.ones(n, bool)
    alive[lo:hi] = False
    t_b = [truth(q) for q in batches]
    t_tag = truth(q_tag, valid=(tags & np.uint32(tag_mask)) != 0)
    t_del = truth(q_del, valid=alive)
    truth.release()

    st, build_s = build_store(cfg, x, tags, seal_rows)
    log(f"build: {st.n_segments} segments, "
        f"{sum(s.index.grains.n_grains for s in st._segments)} grains, "
        f"cap={st._segments[0].index.grains.cap}, {build_s:.1f}s")

    plane = get_scan_plane(None).name
    checks.expect(plane == "fused",
                  f"default scan plane resolved to {plane!r}")
    log_first_calls(st, batches[0])

    agree = []

    def phase(name, q, tr, **kw):
        ids, s_def = timed_search(st, q, **kw)
        ids_ref, s_ref = timed_ref_search(st, q, **kw)
        r_def, r_ref = recall(ids, tr), recall(ids_ref, tr)
        same = same_sets(ids, ids_ref)
        agree.extend(same)
        log(f"{name}: recall@10 default={r_def:.4f} ref={r_ref:.4f} "
            f"top-10 agreement={np.mean(same):.4f} search s "
            f"default={s_def:.3f} ref={s_ref:.3f}")
        checks.expect(r_def >= MIN_RECALL,
                      f"{name} recall@10 >= {MIN_RECALL}")
        checks.expect(abs(r_def - r_ref) <= RECALL_SLACK,
                      f"{name} recall@10 within {RECALL_SLACK} of ref")
        return ids

    for i, (q, tr) in enumerate(zip(batches, t_b)):
        phase(f"mode_b[{i}]", q, tr)
    ids = phase("filtered", q_tag, t_tag, tag_mask=tag_mask)
    hit = ids[ids >= 0]
    checks.expect(bool(np.all(tags[hit] & np.uint32(tag_mask))),
                  "filtered results all carry the tag")
    deleted = st.delete(np.arange(lo, hi))
    log(f"deleted {deleted} ids [{lo}, {hi})")
    ids = phase("after_delete", q_del, t_del)
    checks.expect(not bool(np.any((ids >= lo) & (ids < hi))),
                  "no deleted id returned")
    checks.expect(np.mean(agree) >= MIN_AGREEMENT,
                  f"top-10 ids match ref on {np.mean(agree):.4f} of "
                  f"{len(agree)} queries (>= {MIN_AGREEMENT})")
    log_memory(devices)
    return checks


def plane_bytes_per_device(tree) -> dict:
    import jax
    out = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in getattr(leaf, "addressable_shards", ()):
            out[shard.device] = out.get(shard.device, 0) + shard.data.nbytes
    return out


def smoke_four_chips(devices, *, n: int, seal_rows: int, n_grains: int,
                     nprobe: int, batch: int, n_batches: int,
                     seed: int, small_rows: int = 512,
                     small_segments: int = 8) -> Checks:
    from repro.core import HNTLConfig
    from repro.core.store import VectorStore
    from repro.launch.mesh import make_search_mesh

    checks = Checks()
    mesh = make_search_mesh(4)
    cfg = HNTLConfig(n_grains=n_grains, nprobe=nprobe)
    x, tags = make_corpus(n, cfg.d, seed)
    batches = query_batches(x, n_batches, batch, seed)
    log(f"corpus: N={n} d={cfg.d} n_grains/segment={n_grains} "
        f"nprobe={nprobe} (per shard on the mesh) batch={batch} "
        f"mesh={dict(mesh.shape)}")
    truth = Truth(x)
    truths = [truth(q) for q in batches]
    truth.release()
    st, build_s = build_store(cfg, x, tags, seal_rows)
    log(f"build: {st.n_segments} segments, {build_s:.1f}s")

    _, first = timed_search(st, batches[0], mesh=mesh)
    _, warm = timed_search(st, batches[0], mesh=mesh)
    log(f"sharded plane: first call (shard + compile + search) {first:.1f}s,"
        f" warm call {warm:.3f}s")
    for i, (q, tr) in enumerate(zip(batches, truths)):
        ids_1, s_1 = timed_search(st, q)
        ids_4, s_4 = timed_search(st, q, mesh=mesh)
        r_1, r_4 = recall(ids_1, tr), recall(ids_4, tr)
        log(f"batch[{i}]: recall@10 single-device={r_1:.4f} "
            f"sharded={r_4:.4f} search s single={s_1:.3f} "
            f"sharded={s_4:.3f}")
        checks.expect(r_4 >= r_1 - RECALL_SLACK,
                      f"batch[{i}] sharded recall@10 >= single-device - "
                      f"{RECALL_SLACK}")

    entry = st._sharded_for(tuple(st._segments), mesh, "model")
    per_dev = plane_bytes_per_device(entry["plane"])
    total = sum(per_dev.values())
    log(f"sharded plane bytes: total={total}")
    for d in devices[:4]:
        log(f"  {d}: {per_dev.get(d, 0)}")
    checks.expect(all(per_dev.get(d, 0) >= 0.2 * total for d in devices[:4]),
                  "each of the 4 chips holds >= 20% of the sharded plane")
    log_memory(devices[:4])

    # exhaustive knobs: the sharded plane reduces to exact filtered search
    small_cfg = HNTLConfig(n_grains=4, nprobe=4, pool=small_rows)
    small = VectorStore(small_cfg, seal_threshold=small_rows)
    xs = x[:small_rows * small_segments]
    for lo in range(0, xs.shape[0], small_rows):
        small.add(xs[lo:lo + small_rows])
    qs = batches[0][:16]
    knobs = dict(nprobe=sum(s.index.grains.n_grains
                            for s in small._segments),
                 pool=2 * small.n_vectors)
    ids_1, _ = timed_search(small, qs, **knobs)
    ids_4, _ = timed_search(small, qs, mesh=mesh, **knobs)
    checks.expect(np.array_equal(ids_1, ids_4),
                  f"exhaustive small store ({small.n_vectors} vectors): "
                  "sharded ids bit-identical to single-device")
    return checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the store's main path on one chip; 4: the "
                         "grain-sharded plane on four")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = tpu_devices(args.chips)
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}")

    sizes = dict(n=N_VECTORS, seal_rows=SEAL_ROWS, n_grains=N_GRAINS,
                 nprobe=NPROBE, batch=BATCH, n_batches=N_BATCHES,
                 seed=args.seed)
    t0 = time.perf_counter()
    if args.chips == 1:
        checks = smoke_one_chip(devices, delete_slice=DELETE_SLICE, **sizes)
    else:
        checks = smoke_four_chips(devices, **sizes)
    log(f"total {time.perf_counter() - t0:.1f}s")
    if checks.failed:
        log(f"FAILED: {checks.failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
