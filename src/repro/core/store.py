"""Aperon log-structured memory layer (paper §1-§2).

Grains are self-contained, so the index maps onto immutable *segments*
(Memory SSTables).  This module provides the data-plane semantics the paper
claims graph indexes cannot offer cheaply:

- **append without re-wiring**: new vectors accumulate in a mutable *memtable*
  scanned exactly; a ``seal()`` freezes it into an immutable HNTL segment.
  Sealed segments are never modified — no global graph re-wiring, ever.
- **fused multi-segment search**: sealed segments are lazily padded to a
  common (G, cap) shape and stacked into one :class:`StackedSegments`
  super-index; a search over any number of segments is then a *single*
  jitted dispatch (`planner.search_stacked`) — global routing over the
  concatenated centroid plane, one vmapped Block-SoA scan, one merged
  candidate pool, one exact re-rank — instead of a Python loop paying one
  dispatch + host sync per segment.
- **compaction**: ``compact()`` merges small sealed segments size-tiered
  (LSM style) into one rebuilt HNTL segment with remapped global ids,
  bounding both the segment count and the padding waste of the stack.
- **zero-copy branching**: a branch is a new manifest that *references* the
  same immutable segments (copy-on-write).  Forks cost O(1) and share all
  storage — the paper's "parallel counterfactual simulations".
- **snapshots**: a snapshot is a frozen manifest (segment refs + a captured
  view of the memtable rows), stable across later seals.
- **mixed recall**: each record can carry a symbolic ``tag`` bitmask and a
  timestamp; predicates are evaluated *in-situ* inside the sequential scan
  (extra_mask) and pushed down into routing (grains with zero matching
  records are never probed), not as a post-filter.
- **mutation lifecycle**: ``delete(ids)`` tombstones records, ``upsert``
  writes a new version that shadows every older one, and records can carry
  a TTL.  None of these touch a sealed segment: liveness is a host-side
  (gid, seq) table per manifest, materialised per mutation epoch as a
  [G, cap] bitmap that rides the same in-situ predicate path as tag/ts
  through BOTH the fused and the grain-sharded plane — a delete is visible
  in the very next one-dispatch search without re-stacking anything.
  ``compact()`` is where tombstones are physically reclaimed: dead and
  expired rows are dropped from the merged segment, shrinking the stacked
  plane.  Mutations are manifest-scoped like everything else: snapshots
  keep returning deleted rows' last captured state, and a branch's deletes
  never leak into its parent (each fork copies the liveness table).
- **tiered cold storage**: sealed segments optionally spill raw vectors to a
  numpy memmap file (the paper's SSD/mmap tier); Mode B re-rank reads the
  merged candidate pool from it.

The scan/search data plane is jitted JAX; manifest bookkeeping is plain
Python (build-time / control-plane, exactly like Aperon's Rust control code).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import itertools
import math
import os
import tempfile
import threading
import time
import uuid
import weakref
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import index as index_mod
from . import maintenance
from . import planner
from . import residency
from . import routing
from . import spans
from .types import (BIG, HNTLConfig, HNTLIndex, GrainStore, RoutingPlane,
                    SearchResult, ShardedStackedSegments, StackedSegments)

_BIG = np.float32(BIG)


@dataclasses.dataclass(frozen=True)
class Segment:
    """An immutable sealed segment: HNTL index + optional cold raw tier.

    ``id_map`` is set on *compacted* segments, whose member global ids are no
    longer a contiguous [id_base, id_base + n) range: it maps the segment's
    local row r to its global id.  Plain sealed segments keep id_map=None
    and the affine id_base + r mapping.
    """

    seg_id: int
    index: HNTLIndex                 # raw=None when cold-tiered
    n: int
    id_base: int                     # global id offset of this segment
    tags: Optional[np.ndarray]       # [n] u32
    ts: Optional[np.ndarray]         # [n] f32
    cold_path: Optional[str] = None  # memmap file with raw vectors
    d: int = 0
    id_map: Optional[np.ndarray] = None  # [n] i64 — local row -> global id
    seq: Optional[np.ndarray] = None     # [n] i64 — per-row insert sequence
    expire: Optional[np.ndarray] = None  # [n] f64 — absolute TTL deadline
                                         # (None = no TTLs in this segment)

    def raw_vectors(self) -> np.ndarray:
        if self.index.raw is not None:
            return np.asarray(self.index.raw)
        return np.memmap(self.cold_path, dtype=np.float32, mode="r",
                         shape=(self.n, self.d))

    def global_ids(self) -> np.ndarray:
        """Global id of every local row, in build order.  [n] i64."""
        if self.id_map is not None:
            return self.id_map
        return np.arange(self.id_base, self.id_base + self.n, dtype=np.int64)

    def global_seqs(self) -> np.ndarray:
        """Insert sequence of every local row.  For segments sealed before
        any upsert, gid == seq (both assigned monotonically by add)."""
        if self.seq is not None:
            return self.seq
        return self.global_ids()

    def map_local(self, local_ids: np.ndarray) -> np.ndarray:
        """Translate local candidate ids to global ids (-1 stays -1)."""
        if self.id_map is None:
            return np.where(local_ids >= 0, local_ids + self.id_base, -1)
        return np.where(local_ids >= 0,
                        self.id_map[np.maximum(local_ids, 0)], -1)


def _unlink_quiet(path: str) -> None:
    with contextlib.suppress(OSError):
        os.unlink(path)


# Cold files are refcounted per Segment *object* that addresses them: a
# maintenance epoch derives a new Segment sharing the old one's cold file
# (only grain panels are rewritten), so the file must outlive whichever of
# the two dies first.  The counter is mutated from seal/compact/maintain on
# the owning store AND from tenancy/GC paths (finalizers run on whatever
# thread triggers collection), so every mutation goes through _COLD_LOCK.
# RLock, not Lock: a finalizer can fire via GC *inside* a locked region on
# the same thread, and _release_cold must not deadlock against it.
_COLD_LOCK = threading.RLock()
_COLD_REFS: "collections.Counter" = collections.Counter()


def _release_cold(path: str) -> None:
    with _COLD_LOCK:
        _COLD_REFS[path] -= 1
        reclaim = _COLD_REFS[path] <= 0
        if reclaim:
            del _COLD_REFS[path]
    if reclaim:
        _unlink_quiet(path)


def _reclaim_cold_on_gc(seg: "Segment", path: str) -> None:
    """Delete a segment's cold memmap when the LAST Segment addressing it
    dies.

    Branches, snapshots and the stack cache all hold the same Segment
    *object*, so tying file lifetime to object lifetime is exactly the CoW
    contract: a compacted-away segment's file survives for as long as any
    manifest can still search it, then is reclaimed — cold_dir stays
    bounded under periodic compaction instead of accumulating dead tiers.
    Maintenance-derived segments share their parent's file; the refcount
    keeps it alive until both the parent (old manifests) and the repaired
    child are gone.  (POSIX: a concurrently open memmap keeps reading
    after the unlink.)

    Acquire + finalizer registration are one atomic step: if the finalizer
    cannot be registered the acquired count is rolled back, so the pair can
    never leak a pin without an owner to release it.
    """
    with _COLD_LOCK:
        _COLD_REFS[path] += 1
        try:
            weakref.finalize(seg, _release_cold, path)
        except BaseException:
            _COLD_REFS[path] -= 1
            raise


@contextlib.contextmanager
def _cold_construction(path: Optional[str]):
    """Exception-safe window between writing a cold file and handing its
    lifetime to a Segment finalizer.

    ``seal()``/``_merge_segments()`` write the cold memmap *before* the
    Segment that owns it exists; if construction fails in between, nothing
    ever registers a release and the file is orphaned on disk forever.
    This guard owns the file for the window: the body calls ``adopt(seg)``
    (-> :func:`_reclaim_cold_on_gc`) on success, and any exception before
    adoption unlinks the un-owned file.  ``path=None`` (warm tier) is a
    no-op pass-through.
    """
    if path is None:
        yield lambda seg: None
        return
    adopted = []

    def adopt(seg: "Segment") -> None:
        _reclaim_cold_on_gc(seg, path)
        adopted.append(True)

    try:
        yield adopt
    except BaseException:
        if not adopted:
            # Unlink only when NO Segment pins the path: a maintenance
            # child failing mid-construction must not take its parent's
            # (shared, still-referenced) cold file down with it.
            with _COLD_LOCK:
                orphan = _COLD_REFS[path] <= 0
                if orphan:
                    _COLD_REFS.pop(path, None)
            if orphan:
                _unlink_quiet(path)
        raise


@functools.partial(jax.jit, static_argnames=("topk",))
def _rerank_pool(cand, q, ok, *, topk: int):
    """Device clone of the warm Mode B tail of ``planner._candidate_epilogue``
    for the tiered paged path: exact f32 re-rank of an already-merged
    candidate pool.  The arithmetic (squared-L2 reduce over a [Q, pool, d]
    gather, BIG-masked, ``top_k`` of the negated dists) must stay identical
    to the epilogue's — the tiered plane's bit-for-bit parity with the
    all-warm fused oracle depends on it.  Returns (pos [Q, topk], exact
    dists [Q, topk])."""
    exact = jnp.sum((cand - q[:, None, :]) ** 2, axis=-1)
    exact = jnp.where(ok, exact, BIG)
    neg, pos = jax.lax.top_k(-exact, topk)
    return pos, -neg


def _plane_key(scan_impl: Optional[str]) -> str:
    """Canonical ScanPlane name for plane-cache keys: aliases of the same
    backend (None, "auto", and whatever they resolve to) share ONE cached
    device plane instead of duplicating the stack per spelling."""
    from . import scanplane
    return scanplane.get_scan_plane(scan_impl).name


def _finalize(ids: np.ndarray, d: np.ndarray, topk: int) -> SearchResult:
    """Merge candidate pools into a fixed [Q, topk] result.

    Slots whose distance carries the pruned sentinel (filtered-out, padding,
    or fewer candidates than topk) come back as id -1, never as a
    real-looking id — callers filter hits with ``id >= 0``.
    """
    order = np.argsort(d, axis=1)[:, :topk]
    ids = np.take_along_axis(ids, order, axis=1)
    d = np.take_along_axis(d, order, axis=1)
    ids = np.where(d < BIG / 2, ids, -1)
    if ids.shape[1] < topk:
        pad = topk - ids.shape[1]
        ids = np.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
        d = np.pad(d, ((0, 0), (0, pad)), constant_values=_BIG)
    return SearchResult(ids=jnp.asarray(ids), dists=jnp.asarray(d))


@dataclasses.dataclass(frozen=True)
class Manifest:
    """Immutable snapshot of a store: segment refs + frozen memtable view.

    The memtable rows are captured by reference (tuple of the row arrays),
    not by watermark alone: a later ``seal()`` clears the store's live
    memtable, and a snapshot must keep returning exactly what it saw.

    Mutation state is captured the same way: ``mut_gid``/``mut_seq`` are the
    (sorted) liveness overrides at snapshot time — gid g's live version is
    mut_seq[i] where mut_gid[i] == g (−1 = deleted), any gid absent from the
    table is live at its only version.  Later deletes/upserts in the store
    bump its epoch and never alter a captured manifest.
    """

    segments: tuple                  # tuple[Segment, ...]
    mem_n: int                       # number of captured memtable rows
    mem: tuple = ()                  # tuple[np.ndarray] — captured rows
    mem_tags: tuple = ()             # tuple[int]
    mem_ts: tuple = ()               # tuple[float]
    mem_base: int = 0                # global id of the first captured row
    mem_ids: tuple = ()              # tuple[int] — gid of each captured row
    mem_seq: tuple = ()              # tuple[int] — insert seq of each row
    mem_expire: tuple = ()           # tuple[float] — TTL deadline (inf=none)
    mut_gid: Optional[np.ndarray] = None  # [M] i64 sorted mutated gids
    mut_seq: Optional[np.ndarray] = None  # [M] i64 live seq (-1 = deleted)
    writer: str = ""                 # identity of the capturing store
    epoch: int = 0                   # mutation epoch at capture time
    maint_epoch: int = 0             # maintenance epoch at capture time
    #                                  (the segment refs above pin the
    #                                  pre-repair structures either way)


def _live_rows(mut_gid: Optional[np.ndarray], mut_seq: Optional[np.ndarray],
               gids: np.ndarray, seqs: np.ndarray) -> Optional[np.ndarray]:
    """Tombstone/shadow verdict for physical rows.  None = all live.

    A row (gid g, seq s) is dead iff g appears in the mutation table with a
    live seq != s — i.e. it was deleted (live seq -1) or shadowed by a
    later upsert of the same gid (LSM newest-version-wins).
    """
    if mut_gid is None or len(mut_gid) == 0 or len(gids) == 0:
        return None
    pos = np.minimum(np.searchsorted(mut_gid, gids), len(mut_gid) - 1)
    dead = (mut_gid[pos] == gids) & (mut_seq[pos] != seqs)
    if not dead.any():
        return None
    return ~dead


def _concat_expiry(segments: Sequence["Segment"]) -> Optional[np.ndarray]:
    """Per-row TTL deadlines across segments, or None when no segment
    carries any (the common no-TTL case costs nothing per search)."""
    if all(s.expire is None for s in segments):
        return None
    return np.concatenate(
        [s.expire if s.expire is not None else np.full(s.n, np.inf)
         for s in segments])


# ---------------------------------------------------------------------------
# StackedSegments assembly (host control-plane; runs once per manifest change)
# ---------------------------------------------------------------------------


def _pad_to(a: np.ndarray, shape: tuple, fill) -> np.ndarray:
    out = np.full(shape, fill, dtype=a.dtype)
    out[tuple(slice(0, s) for s in a.shape)] = a
    return out


def stack_segments(segments: Sequence["Segment"], *,
                   device: bool = True) -> StackedSegments:
    """Fuse sealed segments into one :class:`StackedSegments` super-index.

    Every segment's GrainStore is padded to the common (G_max, cap_max)
    envelope, stacked on a leading segment axis, and the (segment, grain)
    axes fused to [S*G_max] so the stack routes/scans as a single HNTLIndex.
    Grain ``ids`` are rewritten to *flat rows* of the concatenated raw tier;
    ``gid_of_row`` carries the flat-row -> global-id translation (i32: the
    fused plane addresses at most 2^31 vectors).

    Padding grains get sizes=0 / valid=False (never routed, never counted)
    and scale=1 (no divide-by-zero in the envelope filter).

    ``device=False`` keeps every leaf a host numpy array — the sharded
    re-layout path stacks on the host and places each leaf directly onto
    its shard, so the full plane never stages through a single device.
    """
    segs = list(segments)
    assert segs, "cannot stack an empty segment list"
    s_n = len(segs)
    g0 = segs[0].index.grains
    gmax = max(s.index.grains.n_grains for s in segs)
    capmax = max(s.index.grains.cap for s in segs)
    k = g0.k
    d = g0.mu.shape[1]
    has_sketch = g0.sketch is not None
    warm = all(s.index.raw is not None for s in segs)
    # Per-grain mixed-precision widths fuse like any grain-axis leaf.  A
    # fixed-width segment in a density stack (off-cfg corner: one store's
    # cfg is uniform) gets its effective qmax spelled out explicitly.
    any_qmax = any(s.index.grains.qmaxg is not None for s in segs)
    qeff_fb = index_mod.int32_safe_qmax(k)

    offsets = np.zeros(s_n + 1, np.int64)
    np.cumsum([s.n for s in segs], out=offsets[1:])

    acc = collections.defaultdict(list)
    for si, seg in enumerate(segs):
        g = seg.index.grains
        assert (g.sketch is not None) == has_sketch, \
            "segments disagree on sketch presence (mixed cfg.s)"
        acc["coords"].append(_pad_to(np.asarray(g.coords),
                                     (gmax, k, capmax), 0))
        acc["res"].append(_pad_to(np.asarray(g.res), (gmax, capmax), 0))
        acc["valid"].append(_pad_to(np.asarray(g.valid),
                                    (gmax, capmax), False))
        local = np.asarray(g.ids, np.int64)
        flat = np.where(local >= 0, local + offsets[si], -1).astype(np.int32)
        acc["ids"].append(_pad_to(flat, (gmax, capmax), -1))
        acc["basis"].append(_pad_to(np.asarray(g.basis), (gmax, d, k), 0.0))
        acc["mu"].append(_pad_to(np.asarray(g.mu), (gmax, d), 0.0))
        acc["scale"].append(_pad_to(np.asarray(g.scale), (gmax,), 1.0))
        acc["res_scale"].append(_pad_to(np.asarray(g.res_scale),
                                        (gmax,), 1.0))
        acc["sizes"].append(_pad_to(np.asarray(seg.index.routing.sizes),
                                    (gmax,), 0))
        tags = (np.asarray(g.tags) if g.tags is not None
                else np.zeros((g.n_grains, g.cap), np.uint32))
        acc["tags"].append(_pad_to(tags, (gmax, capmax), 0))
        ts = (np.asarray(g.ts) if g.ts is not None
              else np.zeros((g.n_grains, g.cap), np.float32))
        acc["ts"].append(_pad_to(ts, (gmax, capmax), 0.0))
        if any_qmax:
            qm = (np.asarray(g.qmaxg, np.int32) if g.qmaxg is not None
                  else np.full(g.n_grains, qeff_fb, np.int32))
            acc["qmaxg"].append(_pad_to(qm, (gmax,), 1))
        if has_sketch:
            s_dim = g.sketch.shape[1]
            acc["sketch"].append(_pad_to(np.asarray(g.sketch),
                                         (gmax, s_dim, capmax), 0))
            acc["sketch_basis"].append(_pad_to(np.asarray(g.sketch_basis),
                                               (gmax, d, s_dim), 0.0))
            acc["sketch_scale"].append(_pad_to(np.asarray(g.sketch_scale),
                                               (gmax,), 1.0))

    put = jnp.asarray if device else (lambda a: a)

    def fuse(name):  # [S, G, ...] -> [S*G, ...]
        a = np.stack(acc[name])
        return put(a.reshape((s_n * gmax,) + a.shape[2:]))

    grains = GrainStore(
        coords=fuse("coords"), res=fuse("res"),
        sketch=fuse("sketch") if has_sketch else None,
        ids=fuse("ids"), valid=fuse("valid"), basis=fuse("basis"),
        mu=fuse("mu"), scale=fuse("scale"), res_scale=fuse("res_scale"),
        sketch_basis=fuse("sketch_basis") if has_sketch else None,
        sketch_scale=fuse("sketch_scale") if has_sketch else None,
        tags=fuse("tags"), ts=fuse("ts"),
        qmaxg=fuse("qmaxg") if any_qmax else None)
    index = HNTLIndex(
        routing=RoutingPlane(centroids=grains.mu, sizes=fuse("sizes")),
        grains=grains,
        raw=put(np.concatenate(
            [np.asarray(s.index.raw) for s in segs])) if warm else None)
    gid_of_row = np.concatenate(
        [s.global_ids() for s in segs]).astype(np.int32)
    return StackedSegments(
        index=index,
        gid_of_row=put(gid_of_row),
        row_offset=put(offsets.astype(np.int32)))


def shard_segments(segments: Sequence["Segment"], n_shards: int):
    """Re-lay-out the stacked super-index for an ``n_shards``-way mesh.

    Builds on :func:`stack_segments`, then makes the layout shard-aligned:

    - the fused grain axis is padded to a multiple of ``n_shards`` with dead
      grains (sizes=0, valid=False) and split into contiguous chunks, one
      chunk per shard;
    - the raw tier is **permuted grain-wise**: shard s's slice holds exactly
      the member rows of the grains in its chunk (each row belongs to
      exactly one grain), padded to a common per-shard row count.  Grain
      ``ids`` are rewritten to rows *local to the owning shard's slice*, so
      the distributed Mode B re-rank never reads another shard's raw tier;
    - ``gid_of_row`` is permuted the same way (local translation to global
      ids before the merge collective).

    Returns ``(plane, perm)``: the :class:`ShardedStackedSegments` pytree
    (host numpy leaves, ready for `distributed.sharding.shard_search_plane`)
    and the host-side ``perm [n_shards*rows_per_shard] i64`` table mapping a
    permuted row back to its original flat row (-1 on padding rows), which
    the cold-tier path uses to resolve candidates to per-segment memmaps.
    """
    assert n_shards >= 1
    # host-only stacking: leaves stay numpy so the only device transfer is
    # shard_search_plane placing each shard's slice on its own device
    stacked = stack_segments(segments, device=False)
    g = stacked.index.grains
    sg = g.n_grains
    g_pad = -(-sg // n_shards) * n_shards - sg
    g_local = (sg + g_pad) // n_shards

    def padg(a, fill):
        a = np.asarray(a)
        if not g_pad:
            return a
        return np.concatenate(
            [a, np.full((g_pad,) + a.shape[1:], fill, a.dtype)])

    ids = padg(g.ids, -1)                       # [Gp, cap] flat raw rows
    valid = padg(g.valid, False)
    gids_unperm = np.asarray(stacked.gid_of_row)
    raw_unperm = (np.asarray(stacked.index.raw)
                  if stacked.index.raw is not None else None)

    owned = [ids[s * g_local:(s + 1) * g_local][
        valid[s * g_local:(s + 1) * g_local]].astype(np.int64)
        for s in range(n_shards)]               # rows per shard, scan order
    rows_per_shard = max(1, max(len(r) for r in owned))
    perm = np.full(n_shards * rows_per_shard, -1, np.int64)
    new_ids = np.full_like(ids, -1)
    lut = np.full(gids_unperm.shape[0], -1, np.int64)
    for s, rows in enumerate(owned):
        perm[s * rows_per_shard:s * rows_per_shard + len(rows)] = rows
        lut[:] = -1
        lut[rows] = np.arange(len(rows))
        ch = ids[s * g_local:(s + 1) * g_local]
        new_ids[s * g_local:(s + 1) * g_local] = np.where(
            ch >= 0, lut[np.maximum(ch, 0)], -1).astype(np.int32)

    keep = np.maximum(perm, 0)
    gid_perm = np.where(perm >= 0, gids_unperm[keep], -1).astype(np.int32)
    has_sketch = g.sketch is not None
    grains = GrainStore(
        coords=padg(g.coords, 0), res=padg(g.res, 0),
        sketch=padg(g.sketch, 0) if has_sketch else None,
        ids=new_ids, valid=valid, basis=padg(g.basis, 0.0),
        mu=padg(g.mu, 0.0), scale=padg(g.scale, 1.0),
        res_scale=padg(g.res_scale, 1.0),
        sketch_basis=padg(g.sketch_basis, 0.0) if has_sketch else None,
        sketch_scale=padg(g.sketch_scale, 1.0) if has_sketch else None,
        tags=padg(g.tags, 0), ts=padg(g.ts, 0.0),
        qmaxg=padg(g.qmaxg, 1) if g.qmaxg is not None else None)
    index = HNTLIndex(
        routing=RoutingPlane(centroids=grains.mu,
                             sizes=padg(stacked.index.routing.sizes, 0)),
        grains=grains,
        raw=raw_unperm[keep] if raw_unperm is not None else None)
    return ShardedStackedSegments(index=index, gid_of_row=gid_perm), perm


class VectorStore:
    """Log-structured vector memory with HNTL-indexed sealed segments."""

    def __init__(self, cfg: HNTLConfig, *, seal_threshold: int = 8192,
                 cold_dir: Optional[str] = None, cold_tier: bool = False,
                 stack_cache_entries: int = 2,
                 device_budget: Optional[int] = None,
                 residency_interval: int = 64,
                 prefetch_grains: int = 64, clock=time.time):
        self.cfg = cfg
        self.seal_threshold = seal_threshold
        self.cold_tier = cold_tier
        self.cold_dir = cold_dir or tempfile.mkdtemp(prefix="aperon_cold_")
        # Tiered residency (core.residency): device_budget caps the HBM
        # bytes spent on resident grain panels; None = the classic all-warm
        # stacked plane.  residency_interval is the admission cadence (every
        # N tiered searches the hot set is re-derived from the accumulated
        # route_wins/touches counters); prefetch_grains is the cold-chunk
        # width of the double-buffered staging pipeline (rounded up to a
        # power of two for bounded dispatch shapes).
        if device_budget is not None and device_budget < 0:
            raise ValueError("device_budget must be >= 0 bytes")
        if residency_interval < 1:
            raise ValueError("residency_interval must be >= 1")
        if prefetch_grains < 1:
            raise ValueError("prefetch_grains must be >= 1")
        self.device_budget = device_budget
        self.residency_interval = int(residency_interval)
        self.prefetch_grains = residency.pow2ceil(prefetch_grains)
        self._segments: list[Segment] = []
        self._mem: list[np.ndarray] = []
        self._mem_tags: list[int] = []
        self._mem_ts: list[float] = []
        self._mem_ids: list[int] = []           # gid per memtable row
        self._mem_seq: list[int] = []           # insert seq per memtable row
        self._mem_expire: list[float] = []      # TTL deadline (inf = none)
        self._next_id = 0
        self._next_seq = 0
        self._next_seg = 0
        self._clock = clock                     # injectable for TTL tests
        # Mutation control plane: gid -> live insert seq (-1 = deleted).
        # Gids absent from the table are live at their only version.  The
        # epoch counts mutations; cached per-plane liveness bitmaps key on
        # (writer, epoch) so a delete invalidates them without re-stacking.
        self._live_seq: dict = {}
        self._epoch = 0
        self._maint_epoch = 0                   # maintenance epochs applied
        self._mut_cache = (-1, None, None)      # (epoch, mut_gid, mut_seq)
        self._cold_tag = uuid.uuid4().hex[:8]   # per-writer cold-file suffix
        # Bounded LRU of fused/sharded search planes, keyed by (manifest
        # segment identity, mesh placement).  Every entry pins a full device
        # copy of the stacked plane (including the concatenated warm raw
        # tier), so the cap must stay tiny: the default 2 covers the common
        # parent+branch / live+snapshot alternation.  Entries keep the
        # segment tuple alive so id()-keys cannot be reused.
        if stack_cache_entries < 1:
            raise ValueError("stack_cache_entries must be >= 1")
        self.stack_cache_entries = stack_cache_entries
        self._stack_cache: "collections.OrderedDict" = \
            collections.OrderedDict()
        # Adaptive-routing probe traffic, keyed like the plane cache by
        # segment identity: accumulated routing-win / active-touch counters
        # over the stacked grain axis ([S*gmax] int64).  Feeds the hub set
        # (top hub_size by wins, always probed) and grain_health.  Bounded
        # alongside the plane cache; a re-stack starts fresh counters.
        self._probe_traffic: "collections.OrderedDict" = \
            collections.OrderedDict()
        self._calls = itertools.count()         # ``call`` of hntl.search
        spans.install_gc_hook()

    # ------------------------------------------------------------- write path
    def _expiry_of(self, ttl, n: int) -> list:
        """Absolute TTL deadlines for n new rows (inf = never expires)."""
        if ttl is None:
            return [np.inf] * n
        now = self._clock()
        ttls = np.broadcast_to(np.asarray(ttl, np.float64), (n,))
        return [now + float(t) for t in ttls]

    def _append_rows(self, vecs, ids, tags, ts, ttl) -> None:
        n = vecs.shape[0]
        self._mem.extend(list(vecs))
        self._mem_tags.extend(list(tags) if tags is not None else [0] * n)
        self._mem_ts.extend(list(ts) if ts is not None else [0.0] * n)
        self._mem_ids.extend(int(i) for i in ids)
        self._mem_seq.extend(range(self._next_seq, self._next_seq + n))
        self._next_seq += n
        self._mem_expire.extend(self._expiry_of(ttl, n))
        if len(self._mem) >= self.seal_threshold:
            self.seal()

    def add(self, vecs: np.ndarray, tags: Optional[Sequence[int]] = None,
            ts: Optional[Sequence[float]] = None,
            ttl=None) -> np.ndarray:
        """Append vectors; returns assigned global ids.

        ttl: optional per-record (scalar or [n]) time-to-live in seconds;
        an expired record vanishes from every search without any rewrite
        and is physically reclaimed at the next compact().
        """
        vecs = np.asarray(vecs, np.float32)
        n = vecs.shape[0]
        ids = np.arange(self._next_id, self._next_id + n, dtype=np.int64)
        self._next_id += n
        self._append_rows(vecs, ids, tags, ts, ttl)
        return ids

    # ---------------------------------------------------------- mutation path
    def delete(self, ids) -> int:
        """Tombstone records by global id (GDPR-style removal, eviction).

        Purely a control-plane write: no segment is touched, no plane is
        re-stacked — the next search of ANY plane (fused or sharded, warm or
        cold, Mode A or B) masks the rows in-scan via the liveness bitmap.
        Physical reclamation happens at compact().  Returns the number of
        ids newly tombstoned (already-dead ids are idempotent no-ops, and
        gids outside the assigned id space are ignored — a stale tombstone
        there would kill the future insert that gets that gid).
        """
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        newly = 0
        for g in ids.tolist():
            if not 0 <= g < self._next_id:
                continue
            if self._live_seq.get(g) != -1:
                newly += 1
            self._live_seq[g] = -1
        if newly:
            self._epoch += 1
        return newly

    def upsert(self, ids, vecs: np.ndarray,
               tags: Optional[Sequence[int]] = None,
               ts: Optional[Sequence[float]] = None,
               ttl=None) -> np.ndarray:
        """Overwrite records in place of their global ids (doc re-embedding).

        LSM semantics: the new version is appended to the memtable under the
        SAME gid with a fresh insert seq, and the liveness table makes every
        older physical row of that gid dead — sealed segments are never
        rewritten, searches see exactly one live version, and compact()
        eventually drops the shadowed rows.  Ids never seen before behave
        like plain inserts (upsert-as-insert).
        """
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        vecs = np.asarray(vecs, np.float32)
        assert ids.shape[0] == vecs.shape[0], (ids.shape, vecs.shape)
        assert (ids >= 0).all(), "upsert needs non-negative gids"
        new_seq = np.arange(self._next_seq, self._next_seq + len(ids))
        for g, s in zip(ids.tolist(), new_seq.tolist()):
            self._live_seq[g] = s
        self._next_id = max(self._next_id, int(ids.max()) + 1)
        self._epoch += 1
        self._append_rows(vecs, ids, tags, ts, ttl)
        return ids

    def _grain_count(self, n: int) -> int:
        """Grain budget for a segment of n rows: the configured G per
        seal_threshold rows, scaled up for (compacted) oversize segments,
        floored so every grain holds at least one block."""
        scale = max(1, -(-n // max(self.seal_threshold, 1)))     # ceil div
        return max(1, min(self.cfg.n_grains * scale,
                          n // max(self.cfg.block, 32)))

    def _write_cold(self, x: np.ndarray, seg_id: int) -> str:
        # the per-instance tag keeps writers disjoint: branches share
        # cold_dir AND the _next_seg counter, so seg_id alone would let a
        # parent and a child overwrite each other's cold files
        path = os.path.join(self.cold_dir,
                            f"seg{seg_id:06d}_{self._cold_tag}.raw")
        mm = np.memmap(path, dtype=np.float32, mode="w+", shape=x.shape)
        mm[:] = x
        mm.flush()
        # flush() only writes the dirty pages into the page cache; the
        # manifest is about to reference this path, so force the bytes to
        # stable storage BEFORE the segment becomes visible — a crash
        # between seal and writeback must not leave a manifest pointing at
        # torn raw bytes.
        fd = os.open(path, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
        return path

    def seal(self) -> Optional[Segment]:
        """Freeze the memtable into an immutable HNTL segment."""
        if not self._mem:
            return None
        x = np.stack(self._mem)
        tags = np.asarray(self._mem_tags, np.uint32)
        ts = np.asarray(self._mem_ts, np.float32)
        gids = np.asarray(self._mem_ids, np.int64)
        seqs = np.asarray(self._mem_seq, np.int64)
        expire = np.asarray(self._mem_expire, np.float64)
        n = x.shape[0]
        cfg = dataclasses.replace(self.cfg, n_grains=self._grain_count(n))
        idx, _ = index_mod.build(x, cfg, tags=tags, ts=ts,
                                 keep_raw=not self.cold_tier)
        cold_path = (self._write_cold(x, self._next_seg)
                     if self.cold_tier else None)
        # pure-add memtables hold a contiguous gid run (affine id_base + r);
        # upserts interleave re-used gids, which need the id_map indirection
        contiguous = bool(
            np.array_equal(gids, np.arange(gids[0], gids[0] + n)))
        with _cold_construction(cold_path) as adopt:
            seg = Segment(
                seg_id=self._next_seg, index=idx, n=n,
                id_base=int(gids[0]) if contiguous else 0,
                tags=tags, ts=ts, cold_path=cold_path, d=x.shape[1],
                id_map=None if contiguous else gids,
                seq=seqs,
                expire=expire if np.isfinite(expire).any() else None)
            adopt(seg)
        self._segments.append(seg)
        self._next_seg += 1
        self._mem, self._mem_tags, self._mem_ts = [], [], []
        self._mem_ids, self._mem_seq, self._mem_expire = [], [], []
        return seg

    # ----------------------------------------------------- grain maintenance
    def _seg_live_rows(self, seg: Segment, mg, ms,
                       now: float) -> Optional[np.ndarray]:
        """[n] bool per raw row of one segment — tombstone/shadow/TTL
        verdict (None = all live), the input every health signal reads."""
        live = _live_rows(mg, ms, seg.global_ids(), seg.global_seqs())
        if seg.expire is not None:
            alive_t = seg.expire > now
            if not alive_t.all():
                live = alive_t if live is None else live & alive_t
        return live

    def grain_health(self, *, now: Optional[float] = None) -> list:
        """Per-grain health stats of every sealed segment (read-only).

        Returns one dict per segment: ``live_cnt`` [G], ``captured`` [G]
        (existing frame over the live rows), ``best`` [G] (refit bound),
        ``drift2`` [G] (squared centroid walk-off) and ``var_live`` [G] —
        the signals ``maintain()`` acts on, exposed for monitoring the
        structural rot the mutation table accumulates between epochs —
        plus the adaptive-routing probe-traffic counters ``route_wins`` [G]
        (queries whose routing winner was this grain) and ``touches`` [G]
        (active probes that landed on it).  Traffic is zeros until an
        ``adaptive=True`` search has run against the current segment set.
        """
        now = self._clock() if now is None else now
        mg, ms = self._mut_arrays()
        traffic = self._probe_traffic.get(
            tuple(id(s) for s in self._segments))
        s_n = max(len(self._segments), 1)
        gmax = (traffic["wins"].shape[0] // s_n) if traffic else 0
        out = []
        for si, seg in enumerate(self._segments):
            stats = maintenance.grain_stats(
                seg, self._seg_live_rows(seg, mg, ms, now))
            g_seg = np.asarray(stats["live_cnt"]).shape[0]
            if traffic is not None and (si + 1) * gmax <= \
                    traffic["wins"].shape[0] and g_seg <= gmax:
                wins = traffic["wins"][si * gmax:si * gmax + g_seg]
                touch = traffic["touches"][si * gmax:si * gmax + g_seg]
            else:
                wins = np.zeros(g_seg, np.int64)
                touch = np.zeros(g_seg, np.int64)
            out.append({k: stats[k] for k in
                        ("live_cnt", "captured", "best", "drift2",
                         "var_live")}
                       | {"seg_id": seg.seg_id, "route_wins": wins,
                          "touches": touch})
        return out

    # ------------------------------------------------ adaptive probe traffic
    def _traffic_for(self, segments: tuple, g_total: int) -> dict:
        """Accumulated probe-traffic counters for one stacked segment set
        (created zeroed on first use).  The entry pins the segment tuple so
        its id()-key cannot be reused, exactly like the plane cache."""
        key = tuple(id(s) for s in segments)
        hit = self._probe_traffic.get(key)
        if hit is None or hit["wins"].shape[0] != g_total:
            hit = {"segments": tuple(segments),
                   "wins": np.zeros(g_total, np.int64),
                   "touches": np.zeros(g_total, np.int64),
                   "queries": 0, "active_probes": 0}
            self._probe_traffic[key] = hit
            while len(self._probe_traffic) > max(4,
                                                 self.stack_cache_entries):
                self._probe_traffic.popitem(last=False)
        else:
            self._probe_traffic.move_to_end(key)
        return hit

    def _purge_probe_traffic(self) -> None:
        """Drop probe-traffic entries pinning segments that left the
        manifest (compact()/maintain() epoch swap).

        The LRU's keys are id()-tuples whose entries pin the segment tuple
        itself — without this purge a replaced Segment (and, through
        ``_COLD_REFS``, its cold file) stays alive until LRU churn happens
        to evict the stale entry, which an idle store never does.  Entries
        for snapshots/branches whose segments are ALL still live stay;
        counters for a segment set that no longer fully exists restart
        from zero if some old manifest searches it again."""
        live = {id(s) for s in self._segments}
        stale = [k for k, hit in self._probe_traffic.items()
                 if any(id(s) not in live for s in hit["segments"])]
        for k in stale:
            del self._probe_traffic[k]

    def _hub_mask_host(self, traffic: dict) -> Optional[np.ndarray]:
        """Current hub set as a [G] bool bitmap over the stacked grain axis
        (None until any traffic exists): the ``cfg.hub_size`` grains with
        the highest accumulated routing wins — persistently high-traffic
        grains every adaptive query probes unconditionally."""
        wins = traffic["wins"]
        if self.cfg.hub_size <= 0 or wins.max(initial=0) <= 0:
            return None
        top = np.argsort(wins, kind="stable")[::-1][:self.cfg.hub_size]
        mask = np.zeros(wins.shape[0], bool)
        mask[top[wins[top] > 0]] = True
        return mask

    def hub_grains(self) -> np.ndarray:
        """Stacked-plane grain indices currently pinned as hubs (sorted;
        empty until adaptive traffic accumulates for the live segment set).
        """
        hit = self._probe_traffic.get(tuple(id(s) for s in self._segments))
        mask = self._hub_mask_host(hit) if hit is not None else None
        if mask is None:
            return np.zeros(0, np.int64)
        return np.nonzero(mask)[0].astype(np.int64)

    def probe_stats(self) -> dict:
        """Read-only adaptive-routing traffic summary for the live segment
        set: total adaptive ``queries``, total ``active_probes`` across
        them, and ``mean_active`` probes/query (0.0 before any traffic)."""
        hit = self._probe_traffic.get(tuple(id(s) for s in self._segments))
        if hit is None or hit["queries"] == 0:
            return {"queries": 0, "active_probes": 0, "mean_active": 0.0}
        return {"queries": hit["queries"],
                "active_probes": hit["active_probes"],
                "mean_active": hit["active_probes"] / hit["queries"]}

    def maintain(self, *, now: Optional[float] = None,
                 policy: Optional[maintenance.MaintenancePolicy] = None
                 ) -> maintenance.MaintenanceReport:
        """Adaptive grain maintenance over all sealed segments.

        Detects unhealthy grains (overfull / underfull / frame-stale — see
        ``core.maintenance``) from the mutation table's live set and
        repairs them: overfull grains split by 2-means, underfull grains
        merge into their nearest neighbour with room (all-dead grains
        retire, fully-dead segments drop), and every touched grain gets its
        mean / PCA basis / quantizer scales re-fit on its live rows.

        Strictly control-plane + copy-on-write: raw tiers and id tables
        are shared with the old segments, untouched grains are copied
        bit-identical, healthy segments keep their identity (their cached
        planes stay valid), snapshots/branches keep their captured
        segments, and ONE new manifest emerges per epoch — so the plane
        cache re-stacks at most once per maintenance epoch.  Runs
        automatically at ``compact()`` time; call directly for on-demand
        repair under streaming drift.
        """
        now = self._clock() if now is None else now
        policy = policy if policy is not None \
            else maintenance.MaintenancePolicy()
        mg, ms = self._mut_arrays()
        qeff = index_mod.int32_safe_qmax(self.cfg.k, self.cfg.coord_bits)
        reports, new_segs, changed = [], [], False
        for seg in self._segments:
            new_seg, rep = maintenance.maintain_segment(
                seg, self._seg_live_rows(seg, mg, ms, now), self.cfg,
                policy, qeff)
            reports.append(rep)
            if new_seg is None:            # every row dead: drop segment
                changed = True
                continue
            if new_seg is not seg:
                changed = True
                if new_seg.cold_path is not None:
                    _reclaim_cold_on_gc(new_seg, new_seg.cold_path)
            new_segs.append(new_seg)
        if changed:
            self._segments = new_segs
            self._maint_epoch += 1
            self._purge_tombstones()
            self._purge_probe_traffic()
        return maintenance.MaintenanceReport(segments=tuple(reports))

    # ------------------------------------------------------------ compaction
    def compact(self, *, fanin: int = 4, tier_factor: int = 4,
                max_rounds: int = 16, now: Optional[float] = None,
                maintain: bool = True,
                policy: Optional[maintenance.MaintenancePolicy]
                = None) -> int:
        """Size-tiered LSM compaction of sealed segments.

        Segments are bucketed into size tiers (tier t holds segments of
        roughly seal_threshold * tier_factor^t rows).  Whenever a tier
        accumulates ``fanin`` members, the ``fanin`` oldest are merged into
        one rebuilt HNTL segment — raw vectors concatenated, grains
        re-partitioned at the merged scale, global ids remapped through
        ``id_map`` and the cold tier consolidated into a single memmap.
        Rounds repeat until no tier is full (a merge can cascade upward).

        This is also where mutations are physically reclaimed: tombstoned
        rows, upsert-shadowed versions and rows whose TTL passed (as of
        ``now``, default the store clock) are DROPPED from the merged
        segment, so the stacked plane and the cold tier actually shrink.
        Tombstones whose gid no longer exists anywhere in this store are
        purged from the liveness table afterwards.

        Keeps the segment count O(fanin * log_tier_factor(N)) so the stacked
        search plane stays small and its padding waste bounded.  Compaction
        is copy-on-write like every other manifest op: older snapshots and
        branches keep referencing the pre-merge segments (and their own
        captured liveness tables).

        Unless ``maintain=False``, a grain maintenance pass (see
        :meth:`maintain`) runs after the merges: merged segments are
        healthy by construction (fresh partition over their live rows), so
        this repairs exactly the segments compaction did NOT touch — the
        ones whose grains have been rotting under deletes/upserts since
        they sealed.

        Returns the number of merges performed.
        """
        if fanin < 2:
            raise ValueError(f"fanin must be >= 2, got {fanin}")
        if tier_factor < 2:
            raise ValueError(f"tier_factor must be >= 2, got {tier_factor}")
        now = self._clock() if now is None else now
        merges = 0
        for _ in range(max_rounds):
            if not self._compact_once(fanin, tier_factor, now):
                break
            merges += 1
        if merges:
            self._purge_tombstones()
        if maintain:
            self.maintain(now=now, policy=policy)
        return merges

    def _tier_of(self, n: int, tier_factor: int) -> int:
        t, size = 0, max(self.seal_threshold, 1)
        while n >= size * tier_factor:
            size *= tier_factor
            t += 1
        return t

    def _compact_once(self, fanin: int, tier_factor: int, now: float) -> bool:
        tiers: dict[int, list[Segment]] = collections.defaultdict(list)
        for seg in self._segments:
            tiers[self._tier_of(seg.n, tier_factor)].append(seg)
        for t in sorted(tiers):
            if len(tiers[t]) < fanin:
                continue
            group = sorted(tiers[t], key=lambda s: s.seg_id)[:fanin]
            merged = self._merge_segments(group, now)
            gone = {id(s) for s in group}
            pos = min(i for i, s in enumerate(self._segments)
                      if id(s) in gone)
            kept = [s for s in self._segments if id(s) not in gone]
            if merged is not None:             # every row was dead/expired
                kept.insert(pos, merged)
            self._segments = kept
            self._purge_probe_traffic()
            return True
        return False

    def _mut_arrays(self):
        """The liveness table as sorted (gid, seq) arrays, cached per epoch
        (the vectorised form every per-row liveness check runs on)."""
        if self._mut_cache[0] != self._epoch:
            if self._live_seq:
                mg = np.fromiter(self._live_seq.keys(), np.int64,
                                 len(self._live_seq))
                ms = np.fromiter(self._live_seq.values(), np.int64,
                                 len(self._live_seq))
                order = np.argsort(mg)
                self._mut_cache = (self._epoch, mg[order], ms[order])
            else:
                self._mut_cache = (self._epoch, None, None)
        return self._mut_cache[1], self._mut_cache[2]

    def _merge_segments(self, group: Sequence[Segment],
                        now: float) -> Optional[Segment]:
        """Rebuild ``group`` as one segment with remapped global ids,
        dropping tombstoned / shadowed / TTL-expired rows (reclamation).
        Returns None when nothing in the group survives."""
        x = np.concatenate([np.asarray(s.raw_vectors(), np.float32)
                            for s in group])
        gids = np.concatenate([s.global_ids() for s in group])
        seqs = np.concatenate([s.global_seqs() for s in group])
        expire = _concat_expiry(group)
        tags = np.concatenate(
            [s.tags if s.tags is not None else np.zeros(s.n, np.uint32)
             for s in group])
        ts = np.concatenate(
            [s.ts if s.ts is not None else np.zeros(s.n, np.float32)
             for s in group])
        mg, ms = self._mut_arrays()
        keep = _live_rows(mg, ms, gids, seqs)
        keep = np.ones(len(gids), bool) if keep is None else keep.copy()
        if expire is not None:
            keep &= expire > now
        if not keep.all():
            x, gids, seqs, tags, ts = (a[keep] for a in
                                       (x, gids, seqs, tags, ts))
            expire = expire[keep] if expire is not None else None
        if x.shape[0] == 0:
            return None
        n, d = x.shape
        cfg = dataclasses.replace(self.cfg, n_grains=self._grain_count(n))
        idx, _ = index_mod.build(x, cfg, tags=tags, ts=ts,
                                 keep_raw=not self.cold_tier)
        cold_path = (self._write_cold(x, self._next_seg)
                     if self.cold_tier else None)
        with _cold_construction(cold_path) as adopt:
            seg = Segment(seg_id=self._next_seg, index=idx, n=n, id_base=0,
                          tags=tags, ts=ts, cold_path=cold_path, d=d,
                          id_map=gids.astype(np.int64), seq=seqs,
                          expire=expire if expire is not None
                          and np.isfinite(expire).any() else None)
            adopt(seg)
        self._next_seg += 1
        return seg

    def _purge_tombstones(self) -> None:
        """Drop liveness entries whose gid no longer exists anywhere in THIS
        store (compaction reclaimed every physical row).  Snapshots and
        branches are unaffected — they captured their own tables."""
        if not self._live_seq:
            return
        present = [s.global_ids() for s in self._segments]
        present.append(np.asarray(self._mem_ids, np.int64))
        alive = np.unique(np.concatenate(present)) if present else \
            np.empty(0, np.int64)
        mg = np.fromiter(self._live_seq.keys(), np.int64,
                         len(self._live_seq))
        gone = mg[~np.isin(mg, alive)]
        if len(gone):
            for g in gone.tolist():
                del self._live_seq[g]
            self._epoch += 1

    # ---------------------------------------------------------- control plane
    def snapshot(self) -> Manifest:
        mg, ms = self._mut_arrays()
        return Manifest(segments=tuple(self._segments),
                        mem_n=len(self._mem), mem=tuple(self._mem),
                        mem_tags=tuple(self._mem_tags),
                        mem_ts=tuple(self._mem_ts),
                        mem_base=self._next_id - len(self._mem),
                        mem_ids=tuple(self._mem_ids),
                        mem_seq=tuple(self._mem_seq),
                        mem_expire=tuple(self._mem_expire),
                        mut_gid=mg, mut_seq=ms,
                        writer=self._cold_tag, epoch=self._epoch,
                        maint_epoch=self._maint_epoch)

    def branch(self, *,
               seal_threshold: Optional[int] = None) -> "VectorStore":
        """Zero-copy fork: new store sharing all sealed segments (CoW).

        The liveness table is *copied*: the child starts from the parent's
        mutation state, but neither side's later deletes/upserts leak into
        the other (each writer owns its own (writer, epoch) lineage).

        ``seal_threshold`` overrides the child's memtable budget (the
        tenant registry caps per-tenant memtables this way: overflowing the
        budget force-seals instead of growing without bound)."""
        child = VectorStore(self.cfg,
                            seal_threshold=self.seal_threshold
                            if seal_threshold is None else seal_threshold,
                            cold_dir=self.cold_dir, cold_tier=self.cold_tier,
                            stack_cache_entries=self.stack_cache_entries,
                            device_budget=self.device_budget,
                            residency_interval=self.residency_interval,
                            prefetch_grains=self.prefetch_grains,
                            clock=self._clock)
        child._segments = list(self._segments)        # shared immutable refs
        child._mem = list(self._mem)                  # memtable copied (small)
        child._mem_tags = list(self._mem_tags)
        child._mem_ts = list(self._mem_ts)
        child._mem_ids = list(self._mem_ids)
        child._mem_seq = list(self._mem_seq)
        child._mem_expire = list(self._mem_expire)
        child._next_id = self._next_id
        child._next_seq = self._next_seq
        child._next_seg = self._next_seg
        child._live_seq = dict(self._live_seq)        # isolated mutations
        child._epoch = self._epoch
        child._maint_epoch = self._maint_epoch  # lineage continues; later
        #                                         maintain() on either side
        #                                         stays isolated (CoW segs)
        return child

    @property
    def n_vectors(self) -> int:
        """Physical rows (live + tombstoned-but-unreclaimed)."""
        return sum(s.n for s in self._segments) + len(self._mem)

    def n_live(self, now: Optional[float] = None) -> int:
        """Records a search can return: physical rows minus tombstoned,
        upsert-shadowed and TTL-expired ones."""
        now = self._clock() if now is None else now
        mg, ms = self._mut_arrays()
        total = 0
        for gids, seqs, expire in [
                (s.global_ids(), s.global_seqs(), s.expire)
                for s in self._segments] + [
                (np.asarray(self._mem_ids, np.int64),
                 np.asarray(self._mem_seq, np.int64),
                 np.asarray(self._mem_expire, np.float64))]:
            keep = _live_rows(mg, ms, gids, seqs)
            keep = np.ones(len(gids), bool) if keep is None else keep.copy()
            if expire is not None and len(gids):
                keep &= np.asarray(expire) > now
            total += int(keep.sum())
        return total

    @property
    def n_segments(self) -> int:
        return len(self._segments)

    @property
    def maintenance_epochs(self) -> int:
        """Maintenance epochs that changed this store's lineage (branches
        inherit the count; snapshots capture it as ``Manifest.maint_epoch``).
        The re-stack accounting contract is ``re-stacks <= manifest
        changes``: each epoch advances this by exactly one, no matter how
        many grains it repaired (benchmarks/drift.py asserts it)."""
        return self._maint_epoch

    # ------------------------------------------------------------- read path
    def _cache_get(self, key):
        hit = self._stack_cache.get(key)
        if hit is not None:
            self._stack_cache.move_to_end(key)
            return hit[1]
        return None

    def _cache_put(self, key, segments: tuple, value):
        self._stack_cache[key] = (tuple(segments), value)
        while len(self._stack_cache) > self.stack_cache_entries:
            self._stack_cache.popitem(last=False)
        return value

    def _stacked_for(self, segments: tuple,
                     scan_impl: Optional[str] = None) -> dict:
        """Stacked super-index for a manifest, rebuilt lazily on change.

        The cached entry also carries the host-side row metadata (flat-row
        gid/seq/TTL tables + a host copy of the grain id panels) that the
        per-epoch liveness bitmap is computed from — mutations never trigger
        a re-stack, they only swap the plane's ``live`` leaf.  The key
        includes the *resolved* ScanPlane backend (None/"auto"/"ref" on CPU
        are one key), so each distinct backend's plane (and its per-epoch
        live leaf) occupies its own LRU slot — switching backends never
        hands one a leaf placed for another."""
        key = (tuple(id(s) for s in segments), _plane_key(scan_impl))
        hit = self._cache_get(key)
        if hit is not None:
            return hit
        with jax.profiler.TraceAnnotation(spans.PLANE_STACK):
            stacked = stack_segments(segments)
            gids = np.asarray(stacked.gid_of_row, np.int64)
            entry = {
                "plane": stacked,
                "offsets": np.asarray(stacked.row_offset, np.int64),
                "gids": gids,
                "ids_host": np.asarray(stacked.index.grains.ids),
                "row_gid": gids,
                "row_seq": np.concatenate(
                    [s.global_seqs() for s in segments]),
                "row_exp": _concat_expiry(segments),
                "row_base": None,          # fused ids ARE global flat rows
                "rules": None,             # single-device: plain device put
                "live": (None, None),      # (epoch key, plane-with-live)
            }
            return self._cache_put(key, segments, entry)

    # ------------------------------------------------------ tiered residency
    def _tiered_for(self, segments: tuple,
                    scan_impl: Optional[str] = None) -> dict:
        """Tiered search plane for a manifest: the grain panels demoted to
        one disk-backed Block-SoA file (``core.residency``), a panel-free
        routing stub on device, and the admission state (per-grain
        route_wins/touches counters + the hot set they elect).

        Shares the plane LRU with the stacked/sharded entries — the cached
        device footprint is the stub + hot mini-plane instead of the full
        stack, which is the entire point.  The panel file is unlinked by the
        TieredPlane finalizer when the entry (or the manifest) dies, exactly
        like a cold raw memmap."""
        key = (tuple(id(s) for s in segments), "tiered",
               _plane_key(scan_impl))
        hit = self._cache_get(key)
        if hit is not None:
            return hit
        with jax.profiler.TraceAnnotation(spans.PLANE_STACK):
            stacked = stack_segments(segments, device=False)
            path = os.path.join(
                self.cold_dir,
                f"panels_{self._cold_tag}_{uuid.uuid4().hex[:8]}.soa")
            tiered = residency.TieredPlane.from_stacked(stacked, path)
            gids = np.asarray(stacked.gid_of_row, np.int64)
            entry = {
                "plane": tiered.routing_stub(),
                "tiered": tiered,
                "offsets": np.asarray(stacked.row_offset, np.int64),
                "gids": gids,
                "ids_host": tiered.panels["ids"],
                "row_gid": gids,
                "row_seq": np.concatenate(
                    [s.global_seqs() for s in segments]),
                "row_exp": _concat_expiry(segments),
                "row_base": None,
                "rules": None,
                "live": (None, None),
                "live_host": (None, None),  # (epoch key, [G, cap] bitmap|None)
                "keep": (None, None, None),  # (filter key, keep, grain_ok)
                "raw_host": None,            # lazy warm-raw tier for Mode B
                "searches": 0,
                # Admission counters, SEPARATE from _probe_traffic: every
                # tiered search feeds them, but _probe_traffic (hub set +
                # probe_stats) only accumulates on adaptive searches —
                # exactly like the all-warm plane, so hub masks and stats
                # never diverge from it.
                "r_wins": np.zeros(tiered.n_grains, np.int64),
                "r_touches": np.zeros(tiered.n_grains, np.int64),
            }
            self._seed_hot(tiered)
            return self._cache_put(key, segments, entry)

    def _plane_entry_for(self, segments: tuple,
                         scan_impl: Optional[str] = None) -> dict:
        """The plane-cache entry a manifest searches under the current
        residency mode (the coalesced serving plane builds its tenant
        bitmaps against this, so tenancy follows the store's tier)."""
        if self.device_budget is not None:
            return self._tiered_for(segments, scan_impl)
        return self._stacked_for(segments, scan_impl)

    def _seed_hot(self, tiered) -> None:
        """Initial admission before any traffic exists: biggest grains
        first (deterministic lexsort tiebreak on grain index)."""
        h = tiered.budget_slots(self.device_budget)
        if h > 0:
            order = np.lexsort((np.arange(tiered.n_grains),
                                -tiered.sizes.astype(np.int64)))
            tiered.set_hot(order[:h])
        else:
            tiered.set_hot(np.zeros(0, np.int64))

    def _update_residency_entry(self, entry: dict) -> bool:
        """Re-elect the hot set from the accumulated admission counters:
        top grains by route_wins + touches under the byte budget (size-
        seeded while no traffic exists).  Eviction is implicit — a grain
        that drops out is simply not copied into the next hot mini-plane
        build.  Returns True when the hot set changed."""
        tiered = entry["tiered"]
        h = tiered.budget_slots(self.device_budget)
        if h <= 0:
            return tiered.set_hot(np.zeros(0, np.int64))
        score = entry["r_wins"] + entry["r_touches"]
        if score.max(initial=0) <= 0:
            score = tiered.sizes.astype(np.int64)
        order = np.lexsort((np.arange(tiered.n_grains), -score))
        return tiered.set_hot(order[:h])

    def update_residency(self) -> bool:
        """Force a hot-set re-election on every cached tiered plane (the
        same admission pass that runs automatically every
        ``residency_interval`` searches).  Returns True when any hot set
        changed.  No-op until a tiered search has built a plane."""
        changed = False
        for key, (_segs, entry) in list(self._stack_cache.items()):
            if len(key) == 3 and key[1] == "tiered":
                changed |= self._update_residency_entry(entry)
        return changed

    def residency_stats(self) -> dict:
        """Read-only residency counters (zeros until a tiered search has
        built a plane).  Geometry (grains / hot set / budget unit) comes
        from the live segment set's plane when cached — else from the
        busiest tiered entry (the coalesced serving plane searches tenant
        UNION manifests, which never equal the base store's own set).
        Traffic counters (staged bytes, chunk dispatches, paged queries,
        searches) aggregate over every cached tiered plane."""
        out = {"n_grains": 0, "hot_grains": 0, "hot_bytes": 0,
               "panel_bytes_per_grain": 0, "staged_bytes": 0,
               "chunk_dispatches": 0, "paged_queries": 0,
               "hot_epochs": 0, "searches": 0}
        geom, geom_live, busiest = None, False, -1
        for key, (segs, entry) in self._stack_cache.items():
            if len(key) != 3 or key[1] != "tiered":
                continue
            t = entry["tiered"]
            out["staged_bytes"] += t.staged_bytes
            out["chunk_dispatches"] += t.chunk_dispatches
            out["paged_queries"] += t.paged_queries
            out["searches"] += entry["searches"]
            is_live = segs == tuple(self._segments)
            if is_live and not geom_live \
                    or geom is None \
                    or (not geom_live and entry["searches"] > busiest):
                geom, geom_live = t, geom_live or is_live
                busiest = entry["searches"]
        if geom is not None:
            per = geom.panel_bytes_per_grain()
            out.update(n_grains=geom.n_grains, hot_grains=geom.n_hot,
                       hot_bytes=geom.n_hot * per,
                       panel_bytes_per_grain=per,
                       hot_epochs=geom.hot_epochs)
        return out

    def _tiered_live(self, entry: dict, man: Manifest, now: float):
        """Host [G, cap] liveness bitmap for a tiered entry (None = all
        live), cached per (writer, epoch[, now]) exactly like the device
        leaf of ``_live_plane`` — same row tables, same gather through the
        grain id panels, so the bits are identical to the oracle's leaf."""
        has_ttl = entry["row_exp"] is not None
        key = (man.writer, man.epoch, now if has_ttl else None)
        ck, cached = entry["live_host"]
        if ck == key:
            return key, cached
        with jax.profiler.TraceAnnotation(spans.PLANE_LIVE):
            live_row = _live_rows(man.mut_gid, man.mut_seq,
                                  entry["row_gid"], entry["row_seq"])
            if has_ttl:
                alive_t = entry["row_exp"] > now
                if not alive_t.all():
                    live_row = alive_t if live_row is None \
                        else live_row & alive_t
            bitmap = None
            if live_row is not None:
                ids = np.asarray(entry["ids_host"])
                bitmap = (ids >= 0) & live_row[np.maximum(
                    ids.astype(np.int64), 0)]
            entry["live_host"] = (key, bitmap)
            return key, bitmap

    def _tiered_keep(self, entry: dict, live_key, bitmap, tag_mask,
                     ts_range):
        """Host (keep [G, cap], grain_ok [G]) replica of the in-jit
        mixed-recall pushdown over the memmapped panels, cached per
        (liveness epoch, filter args)."""
        key = (live_key, tag_mask, ts_range)
        ck, keep, gok = entry["keep"]
        if ck == key:
            return keep, gok
        keep, gok = residency.host_keep_mask(entry["tiered"].panels,
                                             bitmap, tag_mask, ts_range)
        entry["keep"] = (key, keep, gok)
        return keep, gok

    def _tiered_raw_host(self, entry: dict, segments: tuple) -> np.ndarray:
        """Concatenated host raw tier for the warm Mode B re-rank (lazy;
        explicit D2H for warm segments, memmap for cold ones)."""
        if entry["raw_host"] is None:
            entry["raw_host"] = np.concatenate(
                [np.asarray(jax.device_get(s.index.raw), np.float32)
                 if s.index.raw is not None
                 else np.asarray(s.raw_vectors(), np.float32)
                 for s in segments])
        return entry["raw_host"]

    def _tiered_pass(self, plane, q_host, qj, plan, *, cap, pool_eff,
                     target, scan_impl, budgets, qeff, tm, tr, tl_host,
                     ti_host, ti_dev, slots):
        """Dispatch one residency pass (hot mini-plane or staged cold
        chunk) through ``search_stacked`` with its compacted probe plan.
        Mode A / translate=False always: every pass contributes raw
        (flat-row, approx-dist) pool columns; the Mode tail runs once on
        the merged pool.  A pass only a FRACTION of the batch needs (the
        cold tail of a skewed mix) dispatches over just those query rows,
        padded to a power of two — per-query arithmetic is independent,
        so the subset scan is bit-equal to scanning everyone against
        dummy slots.  Returns (in-flight SearchResult, pool width,
        qsel | None, active row count)."""
        plan_g, plan_na, w, act_q = plan
        n_act = int(act_q.sum())
        qp = residency.pow2ceil(n_act)
        qsel = None
        if qp < act_q.shape[0]:
            qidx = np.flatnonzero(act_q)
            qsel = np.concatenate(
                [qidx, np.full(qp - n_act, qidx[0], qidx.dtype)])
            plan_g, plan_na = plan_g[qsel], plan_na[qsel]
            qj = jax.device_put(np.ascontiguousarray(q_host[qsel]))
        pool_b = min(pool_eff, w * cap)
        keep_b = min(target, pool_b)
        kw = dict(nprobe=w, envelope_frac=self.cfg.envelope_frac,
                  qeff=qeff, scan_impl=scan_impl, budgets=budgets,
                  tag_mask=tm, ts_range=tr)
        if tl_host is not None:
            # tenant bitmap sliced to the mini-plane's grain axis (+ an
            # all-False row for the dummy grain, which valid=False prunes
            # anyway) — per-slot visibility bits identical to the oracle's
            tl = tl_host[:, np.asarray(slots, np.int64)]
            kw["tenant_live"] = jax.device_put(np.concatenate(
                [tl, np.zeros((tl.shape[0], 1, tl.shape[2]), tl.dtype)],
                axis=1))
            kw["tenant_ix"] = (ti_dev if qsel is None else jax.device_put(
                np.ascontiguousarray(ti_host[qsel].astype(np.int32))))
        probe_plan = (jax.device_put(np.ascontiguousarray(plan_g)),
                      jax.device_put(np.ascontiguousarray(plan_na)))
        res = planner.search_stacked(plane, qj, pool=pool_b, topk=keep_b,
                                     mode="A", translate=False,
                                     probe_plan=probe_plan, **kw)
        return res, keep_b, qsel, n_act

    def _search_segments_tiered(self, q, man, *, topk, mode, tag_mask,
                                ts_range, scan_impl, nprobe, pool, now,
                                budgets=None, tenant_live=None,
                                tenant_ix=None, adaptive=False,
                                probe_margin=1.0, min_probes=1,
                                call_spans=spans.UNTRACED):
        """Paged fused search under a device byte budget.  Returns numpy
        (global_ids [Q, k], dists [Q, k]), bit-identical to the all-warm
        fused plane (modulo exact distance ties).

        Pipeline: (1) ONE ``probe_plan`` routing pass on the panel-free
        stub — the routing pushdown (filters / liveness / tenant) is
        replicated host-side from the memmapped panels and handed in as
        ``grain_mask``; the plan doubles as the prefetch schedule.  (2) The
        plan is split into a hot-set pass over the resident mini-plane and
        cold chunks of ``prefetch_grains`` grains; chunk k+1 is staged
        (disk read + H2D) while chunk k's scan is in flight, and harvesting
        lags one dispatch behind — double-buffered, so at most ~2 chunks of
        cold panels ever occupy HBM.  (3) The per-pass pools merge on the
        host into the oracle's candidate pool, and the Mode A / warm-B /
        cold-B tail runs once on it.  ``budgets`` degrade to per-pass
        knobs here (staged backends cascade within each pass, not across
        the merged pool)."""
        segments = man.segments
        entry = self._tiered_for(segments, scan_impl)
        tiered = entry["tiered"]
        offsets, gids_host = entry["offsets"], entry["gids"]
        cap, g_total = tiered.cap, tiered.n_grains
        q_n = q.shape[0]

        # jit-static knobs, mirroring _fused_statics on the stub geometry
        want_probe = nprobe if nprobe is not None else self.cfg.nprobe
        probe = min(want_probe, g_total)
        want_pool = pool if pool is not None else self.cfg.pool
        pool_eff = min(max(want_pool, topk), probe * cap)
        topk_eff = min(topk, pool_eff)
        qeff = index_mod.int32_safe_qmax(self.cfg.k, self.cfg.coord_bits)
        warm = all(s.index.raw is not None for s in segments)
        if mode == "B":
            target = (pool_eff if budgets is None
                      else min(pool_eff, int(budgets[1])))
        else:
            target = topk_eff

        # host-side routing pushdown (replaces the in-jit filter path)
        live_key, bitmap = self._tiered_live(entry, man, now)
        keep, grain_ok = self._tiered_keep(entry, live_key, bitmap,
                                           tag_mask, ts_range)
        tl_host = (np.asarray(tenant_live)
                   if tenant_live is not None else None)
        ti_host = (np.asarray(tenant_ix, np.int64)
                   if tenant_ix is not None else None)
        gmask_host = residency.host_tenant_mask(tiered.panels, keep,
                                                grain_ok, tl_host, ti_host)
        gmask = (jax.device_put(gmask_host)
                 if gmask_host is not None else None)
        qj = jax.device_put(np.asarray(q, np.float32))
        tm = (jax.device_put(np.uint32(tag_mask))
              if tag_mask is not None else None)
        tr = ((jax.device_put(np.float32(ts_range[0])),
               jax.device_put(np.float32(ts_range[1])))
              if ts_range is not None else None)
        ti_dev = (jax.device_put(np.asarray(ti_host, np.int32))
                  if ti_host is not None else None)
        pkw = dict(cap=cap, pool_eff=pool_eff, target=target,
                   scan_impl=scan_impl, budgets=budgets, qeff=qeff,
                   tm=tm, tr=tr, tl_host=tl_host, ti_host=ti_host,
                   ti_dev=ti_dev)

        # phase 1: one routing pass on the stub = probe plan AND prefetch
        # schedule.  Non-adaptive searches route with margin=inf (the
        # static plan) so results stay bit-identical to the static oracle.
        run_adaptive = adaptive and not math.isinf(probe_margin)
        traffic = None
        call_spans.stage(spans.DISPATCH)
        if run_adaptive:
            traffic = self._traffic_for(segments, g_total)
            hub_host = self._hub_mask_host(traffic)
            hub = (jax.device_put(hub_host)
                   if hub_host is not None else None)
            gids_d, na_d, wins, touches = planner.probe_plan(
                entry["plane"], qj, nprobe=probe,
                probe_margin=probe_margin, min_probes=min_probes,
                hub_mask=hub, grain_mask=gmask)
        else:
            # static plan: bare routing over just the stub's routing
            # sub-tree — identical gids (probe_plan's inf-margin branch IS
            # this call); n_active is the constant P and the traffic
            # counters are host bincounts of the read-back below
            gids_d, _ = planner.static_route(
                entry["plane"].index.routing, qj, nprobe=probe,
                grain_mask=gmask)
            na_d = jax.device_put(np.full(q_n, probe, np.int32))
        pending, results = [], []

        # phase 2a: warm-tier pass, chained straight off the DEVICE plan
        # (cold probes mapped to the dummy slot) and dispatched before the
        # host sync below — the routing read-back and the cold chunk
        # schedule are then planned while the warm scan is in flight.
        if tiered.n_hot > 0:
            plane_h = tiered.hot_plane(bitmap, live_key)
            plan_h = residency.device_plan(tiered.hot_map_dev, gids_d,
                                           dummy_slot=tiered.n_hot)
            pool_b = min(pool_eff, probe * cap)
            keep_b = min(target, pool_b)
            kw = dict(nprobe=probe, envelope_frac=self.cfg.envelope_frac,
                      qeff=qeff, scan_impl=scan_impl, budgets=budgets,
                      tag_mask=tm, ts_range=tr)
            if tl_host is not None:
                tl = tl_host[:, np.asarray(tiered.hot_slots, np.int64)]
                kw["tenant_live"] = jax.device_put(np.concatenate(
                    [tl, np.zeros((tl.shape[0], 1, tl.shape[2]),
                                  tl.dtype)], axis=1))
                kw["tenant_ix"] = ti_dev
            res_h = planner.search_stacked(
                plane_h, qj, pool=pool_b, topk=keep_b, mode="A",
                translate=False, probe_plan=(plan_h, na_d), **kw)
            pending.append((res_h, keep_b, None, q_n))

        call_spans.stage(spans.READBACK)
        if run_adaptive:
            got = jax.device_get((gids_d, na_d, wins, touches))
            gids_h = np.asarray(got[0], np.int32)
            na_h = np.asarray(got[1], np.int32)
            wins_h = np.asarray(got[2], np.int64)
            touch_h = np.asarray(got[3], np.int64)
        else:
            gids_h = np.asarray(jax.device_get(gids_d), np.int32)
            na_h = np.full(q_n, probe, np.int32)
            wins_h = np.bincount(gids_h[:, 0],
                                 minlength=g_total).astype(np.int64)
            touch_h = np.bincount(gids_h.ravel(),
                                  minlength=g_total).astype(np.int64)
        entry["r_wins"] += wins_h
        entry["r_touches"] += touch_h
        if traffic is not None:
            # adaptive searches ONLY — keeps hub masks and probe_stats in
            # lockstep with the all-warm plane (parity contract)
            traffic["wins"] += wins_h
            traffic["touches"] += touch_h
            traffic["queries"] += q_n
            traffic["active_probes"] += int(na_h.sum())
        entry["searches"] += 1
        tiered.paged_queries += q_n
        # re-election applies from the NEXT search — this one's warm pass
        # is already in flight on the current hot set, so the cold chunk
        # schedule below must complement THAT set, not the new one
        hot_map = tiered.hot_map
        if entry["searches"] % self.residency_interval == 0:
            self._update_residency_entry(entry)

        # phase 2b: double-buffered cold chunks
        act = np.arange(probe, dtype=np.int32)[None, :] < na_h[:, None]
        need = act & (hot_map[gids_h] < 0) \
            & (tiered.sizes[gids_h] > 0)
        if gmask_host is not None:
            # probes the pushdown masked scan to BIG in the oracle; the
            # paged plane need not stage their panels to reproduce that
            if gmask_host.ndim == 2:
                need &= np.take_along_axis(gmask_host,
                                           gids_h.astype(np.int64), axis=1)
            else:
                need &= gmask_host[gids_h]
        cold_gids = np.unique(gids_h[need])
        chunks = (residency.chunk_cold(cold_gids, self.prefetch_grains)
                  if len(cold_gids) else [])

        def harvest(item):
            call_spans.stage(spans.READBACK)
            res, keep_b, qsel, n_act = item
            r = np.asarray(jax.device_get(res.ids), np.int64)
            dm = np.asarray(jax.device_get(res.dists), np.float32)
            if qsel is not None:      # scatter a subset pass back to [Q]
                fr = np.full((q_n, keep_b), -1, np.int64)
                fd = np.full((q_n, keep_b), _BIG, np.float32)
                fr[qsel[:n_act]] = r[:n_act]
                fd[qsel[:n_act]] = dm[:n_act]
                r, dm = fr, fd
            results.append((r, dm))

        for ch in chunks:
            if len(pending) >= 2:     # block on k-1, keep k in flight
                harvest(pending.pop(0))
            call_spans.stage(spans.DISPATCH)
            plane_c, member = tiered.chunk_plane(ch, bitmap, live_key)
            plan = residency.compact_probes(gids_h, na_h, member, len(ch))
            if plan is None:
                continue
            pending.append(self._tiered_pass(plane_c, q, qj, plan,
                                             slots=ch, **pkw))
        while pending:
            harvest(pending.pop(0))

        # phase 3: merge the per-pass pools into the oracle's candidate
        # pool (stable ascending-distance order, padded to `target`)
        if results:
            rows = np.concatenate([r for r, _ in results], axis=1)
            dd = np.concatenate([d for _, d in results], axis=1)
        else:
            rows = np.full((q_n, 1), -1, np.int64)
            dd = np.full((q_n, 1), _BIG, np.float32)
        ok = (rows >= 0) & (dd < _BIG / 2)
        dd = np.where(ok, dd, _BIG)
        order = np.argsort(dd, axis=1, kind="stable")[:, :target]
        r_p = np.take_along_axis(rows, order, axis=1)
        d_p = np.take_along_axis(dd, order, axis=1)
        ok_p = np.take_along_axis(ok, order, axis=1)
        if r_p.shape[1] < target:
            padn = target - r_p.shape[1]
            r_p = np.pad(r_p, ((0, 0), (0, padn)), constant_values=-1)
            d_p = np.pad(d_p, ((0, 0), (0, padn)), constant_values=_BIG)
            ok_p = np.pad(ok_p, ((0, 0), (0, padn)),
                          constant_values=False)

        if mode != "B":
            ids = np.where(ok_p, gids_host[np.maximum(r_p, 0)], -1)
            return ids.astype(np.int64), d_p.astype(np.float32)
        if not warm:
            call_spans.stage(spans.COLD_RERANK)
            return self._cold_rerank(q, segments, offsets, gids_host,
                                     r_p, ok_p, topk_eff)
        # warm Mode B: exact re-rank of the merged pool on device, with
        # the raw rows gathered host-side (the stacked raw tier is never
        # device-resident on the tiered plane)
        call_spans.stage(spans.DISPATCH)
        raw = self._tiered_raw_host(entry, segments)
        rows_c = np.maximum(r_p, 0)
        pos, d = _rerank_pool(jax.device_put(raw[rows_c]), qj,
                              jax.device_put(ok_p), topk=topk_eff)
        call_spans.stage(spans.READBACK)
        pos_h = np.asarray(jax.device_get(pos))
        d_h = np.asarray(jax.device_get(d), np.float32)
        ids_pool = np.where(ok_p, gids_host[rows_c], -1)
        ids = np.where(d_h < _BIG / 2,
                       np.take_along_axis(ids_pool, pos_h, axis=1), -1)
        return ids.astype(np.int64), d_h

    def _sharded_for(self, segments: tuple, mesh, grain_axis: str,
                     scan_impl: Optional[str] = None) -> dict:
        """Mesh-sharded plane for a manifest: grain-aligned re-layout
        (`shard_segments`) placed shard-wise on the mesh, plus the host-side
        row metadata the cold path and the liveness bitmap need.  Cached
        alongside the fused plane (same LRU, keyed additionally by mesh
        identity).  Row metadata is PERMUTED like the raw tier, so the
        liveness bitmap lands shard-aligned and Mode B re-rank stays
        shard-local under mutation.

        Maintenance delta path: a refit-only maintenance epoch rewrites
        grain panels but moves no rows (slot layouts kept), so the row
        permutation — and with it the permuted raw tier and id table — is
        unchanged.  When a cached plane for the same mesh proves that
        (identical per-segment row tables + identical perm), its placed
        ``raw``/``gid_of_row`` leaves are reused and only the grain panels
        are re-staged onto the mesh."""
        from ..distributed import sharding as shd
        key = (tuple(id(s) for s in segments), mesh, grain_axis,
               _plane_key(scan_impl))
        hit = self._cache_get(key)
        if hit is not None:
            return hit
        with jax.profiler.TraceAnnotation(spans.PLANE_STACK):
            n_shards = mesh.shape[grain_axis]
            plane, perm = shard_segments(segments, n_shards)
            ids_host = np.asarray(plane.index.grains.ids)
            rules = shd.search_plane_rules(mesh, grain_axis=grain_axis)
            reuse = self._reusable_row_leaves(segments, mesh, grain_axis,
                                              _plane_key(scan_impl), perm)
            plane = shd.shard_search_plane(plane, rules, reuse=reuse)
            offsets = np.zeros(len(segments) + 1, np.int64)
            np.cumsum([s.n for s in segments], out=offsets[1:])
            gids = np.concatenate([s.global_ids() for s in segments])
            seqs = np.concatenate([s.global_seqs() for s in segments])
            exp = _concat_expiry(segments)
            keep = np.maximum(perm, 0)
            g_total = ids_host.shape[0]
            rows_local = len(perm) // n_shards
            entry = {
                "plane": plane,
                "perm": perm,
                "offsets": offsets,
                "gids": gids,
                "ids_host": ids_host,
                "row_gid": np.where(perm >= 0, gids[keep], -1),
                "row_seq": np.where(perm >= 0, seqs[keep], -1),
                "row_exp": (np.where(perm >= 0, exp[keep], np.inf)
                            if exp is not None else None),
                # shard-local panel ids -> permuted global rows: + shard offset
                "row_base": (np.arange(g_total) // (g_total // n_shards)
                             * rows_local),
                "rules": rules,
                "live": (None, None),
            }
            return self._cache_put(key, segments, entry)

    def _reusable_row_leaves(self, segments: tuple, mesh, grain_axis: str,
                             plane_key: str, perm: np.ndarray):
        """Placed ``raw``/``gid_of_row`` leaves of a cached sharded plane
        that are provably identical to the ones about to be placed, or
        None.  Valid iff some cached entry for the same (mesh, grain_axis,
        backend) has the same per-segment row tables (object identity on
        the immutable arrays — maintenance shares them via
        ``dataclasses.replace``) and the same row permutation."""
        for key, (old_segs, entry) in self._stack_cache.items():
            if len(key) != 4 or key[1:] != (mesh, grain_axis, plane_key):
                continue
            if len(old_segs) != len(segments):
                continue
            same_rows = all(
                o.n == s.n and o.index.raw is s.index.raw
                and o.id_map is s.id_map and o.id_base == s.id_base
                and o.seq is s.seq
                for o, s in zip(old_segs, segments))
            if same_rows and np.array_equal(entry["perm"], perm):
                return {"raw": entry["plane"].index.raw,
                        "gid_of_row": entry["plane"].gid_of_row}
        return None

    def _live_plane(self, entry: dict, man: Manifest, now: float):
        """The entry's plane with the manifest-epoch liveness leaf attached.

        Computed host-side from the cached row tables ((gid, seq) vs the
        manifest's mutation table, TTL deadlines vs ``now``), gathered into
        a [G, cap] bitmap through the grain id panels, and swapped in with
        ``dataclasses.replace`` — the plane itself is untouched (NO
        re-stack).  Cached per (writer, epoch): repeat searches at the same
        epoch reuse the placed bitmap; any delete/upsert bumps the epoch and
        invalidates exactly this leaf.  TTL planes add ``now`` to the key
        (a moving clock recomputes; the no-TTL common case never does)."""
        has_ttl = entry["row_exp"] is not None
        key = (man.writer, man.epoch, now if has_ttl else None)
        ck, cached = entry["live"]
        if ck == key:
            return cached
        with jax.profiler.TraceAnnotation(spans.PLANE_LIVE):
            live_row = _live_rows(man.mut_gid, man.mut_seq,
                                  entry["row_gid"], entry["row_seq"])
            if has_ttl:
                alive_t = entry["row_exp"] > now
                if not alive_t.all():
                    live_row = alive_t if live_row is None \
                        else live_row & alive_t
            plane = entry["plane"]
            if live_row is not None:
                ids = entry["ids_host"]
                rows = ids.astype(np.int64)
                if entry["row_base"] is not None:
                    rows = rows + entry["row_base"][:, None]
                bitmap = (ids >= 0) & live_row[np.maximum(rows, 0)]
                if entry["rules"] is not None:
                    from ..distributed import sharding as shd
                    leaf = shd.shard_plane_field(bitmap, entry["rules"],
                                                 "live")
                else:
                    leaf = jnp.asarray(bitmap)
                plane = dataclasses.replace(plane, live=leaf)
            entry["live"] = (key, plane)
            return plane

    def search(self, q: np.ndarray, *, topk: int = 10, mode: str = "B",
               tag_mask: Optional[int] = None,
               ts_range: Optional[tuple] = None,
               manifest: Optional[Manifest] = None,
               scan_impl: Optional[str] = None,
               budgets: Optional[tuple] = None,
               nprobe: Optional[int] = None, pool: Optional[int] = None,
               fused: bool = True, route_mode: str = "global",
               mesh=None, grain_axis: str = "model",
               shard_queries: bool = False,
               adaptive: bool = False,
               probe_margin: Optional[float] = None,
               min_probes: Optional[int] = None,
               now: Optional[float] = None) -> SearchResult:
        """Unified mixed-recall search across sealed segments + memtable.

        All sealed segments are searched by ONE jitted call on the stacked
        super-index (``fused=True``, the default); ``fused=False`` keeps the
        legacy per-segment loop (parity tests, benchmarks).

        tag_mask: keep records with (tag & tag_mask) != 0 (in-situ predicate,
          pushed down into routing).
        ts_range: (lo, hi) keep lo <= ts < hi.
        scan_impl: ScanPlane backend for the candidate stage (see
          ``core.scanplane``): "ref" | "pallas" | "interpret" | "fused" |
          "fused_ref" | "auto" (None = auto).  "fused"/"fused_ref" run the
          streaming scan→select pipeline — candidate state O(Q·pool), no
          probed-panel gather — on every plane (fused, sharded, looped).
        budgets: (b1, b2) per-stage survivor budgets for staged (cascade)
          backends: stage 1 keeps b1 probed slots, stage 2 keeps b2 for the
          exact re-rank.  Validated host-side (b1 >= b2 >= topk); needs a
          staged scan_impl and the fused plane.  On a mesh the budgets are
          per-shard knobs, like nprobe/pool.
        nprobe / pool: override cfg.nprobe / cfg.pool for the fused plane
          (e.g. exhaustive probing for parity checks).
        route_mode: "global" (top-P over all segments' grains at once) or
          "per_segment" (legacy loop probe set, still one dispatch).
        mesh: optional jax Mesh — run the *distributed* search plane: grain
          panels and raw tier sharded along ``grain_axis``, shard-local
          route/scan/pool/re-rank, one all-gather top-k merge collective
          (still a single jitted dispatch).  nprobe/pool become per-shard
          knobs, clamped to each shard's slice of the plane.
        shard_queries: with a mesh, also shard the query batch over the
          mesh's data axis (throughput scaling; the axis size must divide
          the query count, and the axis must exist with size > 1).
        adaptive: per-query adaptive probe counts — after routing, the
          distance-gap stopping rule (``routing.adaptive_prefix``) kills
          probes whose routing bound exceeds (1 + probe_margin)x the
          query's best grain, so easy queries scan 2-3 grains while hard
          queries keep the full nprobe.  Hub grains (the cfg.hub_size
          highest routing-win grains from accumulated traffic) are always
          probed.  Default-off; ``adaptive=False`` is bit-identical to the
          static plane, and ``probe_margin=inf`` short-circuits to it at
          dispatch time.  Needs the fused plane and global routing.
        probe_margin / min_probes: stopping-rule knobs (None = the config's
          ``probe_margin`` / ``min_probes``); setting them without
          ``adaptive=True`` is a validation error.
        now: TTL clock override (default: the store clock).  Records whose
          TTL deadline passed are masked exactly like tombstones.
        """
        q = np.asarray(q, np.float32)
        if q.ndim == 1:
            q = q[None]
        with spans.SearchSpans(q.shape[0], next(self._calls)) as call_spans:
            man = manifest or self.snapshot()
            now = self._clock() if now is None else now
            if budgets is not None:
                from .cascade import check_budgets
                check_budgets(budgets, topk)
                if not fused:
                    raise ValueError(
                        "budgets= needs the fused search plane; the legacy "
                        "looped path has no staged candidate stage")
            routing.check_probe_args(adaptive, probe_margin, min_probes)
            if adaptive:
                if not fused:
                    raise ValueError(
                        "adaptive=True needs the fused search plane; the "
                        "legacy looped path has no ragged-probe stage")
                if route_mode != "global":
                    raise ValueError(
                        "adaptive=True needs route_mode='global' (the "
                        "stopping rule compares one fused routing pass)")
            margin = (self.cfg.probe_margin if probe_margin is None
                      else float(probe_margin))
            minp = (self.cfg.min_probes if min_probes is None
                    else int(min_probes))
            if not fused:
                if mesh is not None:
                    raise ValueError(
                        "mesh= requires the fused search plane")
                if self.device_budget is not None:
                    raise ValueError(
                        "device_budget= (tiered residency) pages through "
                        "the fused stacked plane; fused=False has no paged "
                        "path")
                return self._search_looped(
                    q, man, topk=topk, mode=mode, tag_mask=tag_mask,
                    ts_range=ts_range, scan_impl=scan_impl, now=now,
                    call_spans=call_spans)
            all_ids, all_d = [], []
            if man.segments:
                if mesh is not None:
                    if route_mode != "global":
                        raise ValueError(
                            "the sharded plane routes per shard; route_mode "
                            "overrides only apply to the single-device "
                            "plane")
                    if self.device_budget is not None:
                        raise ValueError(
                            "device_budget= (tiered residency) is "
                            "single-device; the sharded plane (mesh=) keeps "
                            "every shard resident — drop one of the two")
                    ids_s, d_s = self._search_segments_sharded(
                        q, man, topk=topk, mode=mode, tag_mask=tag_mask,
                        ts_range=ts_range, scan_impl=scan_impl,
                        budgets=budgets, nprobe=nprobe, pool=pool,
                        mesh=mesh, grain_axis=grain_axis,
                        shard_queries=shard_queries, now=now,
                        adaptive=adaptive, probe_margin=margin,
                        min_probes=minp, call_spans=call_spans)
                else:
                    ids_s, d_s = self._search_segments_fused(
                        q, man, topk=topk, mode=mode, tag_mask=tag_mask,
                        ts_range=ts_range, scan_impl=scan_impl,
                        budgets=budgets, nprobe=nprobe, pool=pool,
                        route_mode=route_mode, now=now,
                        adaptive=adaptive, probe_margin=margin,
                        min_probes=minp, call_spans=call_spans)
                all_ids.append(ids_s)
                all_d.append(d_s)
            call_spans.stage(spans.FINALIZE)
            return self._merge_with_memtable(q, man, all_ids, all_d, topk,
                                             tag_mask, ts_range, now)

    def _merge_with_memtable(self, q, man: Manifest, all_ids, all_d, topk,
                             tag_mask, ts_range, now) -> SearchResult:
        """Shared result tail of the fused and looped paths: append the
        memtable pool, handle the empty store, finalize to [Q, topk]."""
        mem_ids, mem_d = self._search_memtable(q, man, topk, tag_mask,
                                               ts_range, now)
        if mem_ids is not None:
            all_ids.append(mem_ids)
            all_d.append(mem_d)
        if not all_ids:
            shape = (q.shape[0], topk)
            return SearchResult(ids=jnp.full(shape, -1, jnp.int32),
                                dists=jnp.full(shape, _BIG, jnp.float32))
        return _finalize(np.concatenate(all_ids, axis=1),
                         np.concatenate(all_d, axis=1), topk)

    def _fused_statics(self, segments: tuple, stacked: StackedSegments,
                       topk: int, nprobe: Optional[int],
                       pool: Optional[int], route_mode: str):
        """Clamp the jit-static knobs to the stacked plane's actual shape."""
        s_n = len(segments)
        gmax = stacked.index.grains.n_grains // s_n
        capmax = stacked.index.grains.cap
        want_probe = nprobe if nprobe is not None else self.cfg.nprobe
        if route_mode == "per_segment":
            probe = min(want_probe, gmax)
            n_slots = s_n * probe * capmax
        else:
            probe = min(want_probe, s_n * gmax)
            n_slots = probe * capmax
        # pool >= topk always: Mode B top-k runs over the pool's candidates
        want_pool = pool if pool is not None else self.cfg.pool
        pool_eff = min(max(want_pool, topk), n_slots)
        return probe, pool_eff, min(topk, pool_eff), (s_n, gmax)

    def _search_segments_fused(self, q, man, *, topk, mode, tag_mask,
                               ts_range, scan_impl, nprobe, pool,
                               route_mode, now, budgets=None,
                               tenant_live=None, tenant_ix=None,
                               adaptive=False, probe_margin=1.0,
                               min_probes=1, call_spans=spans.UNTRACED):
        """One jitted search over the stacked plane.  Returns numpy
        (global_ids [Q, k], dists [Q, k]).

        tenant_live [T, G, cap] + tenant_ix [Q] (host bools/ints): per-query
        tenant visibility for the coalesced serving plane — the manifest is
        then the registry's *union* of segments and per-tenant
        liveness/membership arrives through these masks instead of the
        manifest's own mutation table.  ``call_spans``: the calling
        search's spans (``core.spans``), whose stages this path marks."""
        if self.device_budget is not None:
            # Tiered residency: same search, paged data plane.  Routing
            # still sees every grain (the stub is panel-free, not lossy);
            # only panel bytes move tiers, so results stay bit-identical.
            if route_mode != "global":
                raise ValueError(
                    "device_budget= (tiered residency) routes once "
                    "globally; route_mode='per_segment' has no paged plan")
            return self._search_segments_tiered(
                q, man, topk=topk, mode=mode, tag_mask=tag_mask,
                ts_range=ts_range, scan_impl=scan_impl, nprobe=nprobe,
                pool=pool, now=now, budgets=budgets,
                tenant_live=tenant_live, tenant_ix=tenant_ix,
                adaptive=adaptive, probe_margin=probe_margin,
                min_probes=min_probes, call_spans=call_spans)
        segments = man.segments
        entry = self._stacked_for(segments, scan_impl)
        stacked = self._live_plane(entry, man, now)
        offsets, gids_host = entry["offsets"], entry["gids"]
        probe, pool_eff, topk_eff, seg_shape = self._fused_statics(
            segments, stacked, topk, nprobe, pool, route_mode)
        qeff = index_mod.int32_safe_qmax(self.cfg.k, self.cfg.coord_bits)
        # Explicit device placement of the host filter scalars: jnp.uint32(x)
        # on a python int is an *implicit* H2D transfer and trips the
        # HNTL_SANITIZE transfer guard wrapped around this method.
        tm = (jax.device_put(np.uint32(tag_mask))
              if tag_mask is not None else None)
        tr = ((jax.device_put(np.float32(ts_range[0])),
               jax.device_put(np.float32(ts_range[1])))
              if ts_range is not None else None)
        kw = dict(nprobe=probe, envelope_frac=self.cfg.envelope_frac,
                  qeff=qeff, scan_impl=scan_impl, budgets=budgets,
                  route_mode=route_mode, seg_shape=seg_shape, tag_mask=tm,
                  ts_range=tr)
        if tenant_live is not None:
            # Explicit placement again: jnp.asarray with a dtype change
            # (host int64 -> int32) is an implicit H2D under the guard.
            kw["tenant_live"] = jax.device_put(np.asarray(tenant_live))
            kw["tenant_ix"] = jax.device_put(np.asarray(tenant_ix, np.int32))
        qj = jnp.asarray(q)

        if adaptive and not math.isinf(probe_margin):
            return self._adaptive_fused(
                q, qj, segments, stacked, entry, kw, mode=mode,
                pool_eff=pool_eff, topk_eff=topk_eff, probe=probe,
                budgets=budgets, probe_margin=probe_margin,
                min_probes=min_probes,
                tenant_ix_host=(np.asarray(tenant_ix, np.int32)
                                if tenant_ix is not None else None),
                call_spans=call_spans)

        if mode == "B" and stacked.index.raw is None:
            # Cold tier: one jitted approximate scan over the whole stack,
            # then ONE merged-pool exact re-rank from the per-segment memmaps
            # (host gather — the mmap tier is not addressable from jit).
            # Stage budgets cap the useful pool at b2, so the candidate
            # width the host re-rank reads shrinks with it.
            pe = (pool_eff if budgets is None
                  else min(pool_eff, int(budgets[1])))
            call_spans.stage(spans.DISPATCH)
            res = planner.search_stacked(stacked, qj, pool=pool_eff,
                                         topk=pe, mode="A",
                                         translate=False, **kw)
            call_spans.stage(spans.READBACK)
            rows = jax.device_get(res.ids)
            ok = (rows >= 0) & (jax.device_get(res.dists) < BIG / 2)
            call_spans.stage(spans.COLD_RERANK)
            return self._cold_rerank(q, segments, offsets, gids_host,
                                     rows, ok, topk_eff)

        call_spans.stage(spans.DISPATCH)
        res = planner.search_stacked(stacked, qj, pool=pool_eff,
                                     topk=topk_eff, mode=mode, **kw)
        # Explicit D2H: the one sanctioned device->host hop of the warm
        # tier (the final top-k), visible to the transfer guard as such.
        call_spans.stage(spans.READBACK)
        return (np.asarray(jax.device_get(res.ids), np.int64),
                np.asarray(jax.device_get(res.dists), np.float32))

    def _adaptive_fused(self, q, qj, segments, stacked, entry, kw, *, mode,
                        pool_eff, topk_eff, probe, budgets, probe_margin,
                        min_probes, tenant_ix_host=None,
                        call_spans=spans.UNTRACED):
        """Two-phase bucketed adaptive dispatch over the fused plane.

        Phase 1 (``planner.probe_plan``): ONE jitted routing pass applies
        the distance-gap stopping rule + hub pinning and returns each
        query's active-probe prefix plus the traffic counters the hub set
        feeds on.  Phase 2: queries are bucketed host-side by pow-2 probe
        width and each bucket re-enters ``search_stacked`` with its SLICED
        plan — a genuinely smaller static probe width, so easy queries
        scan (and pay for) fewer grain panels instead of merely masking
        them; within a bucket the ragged ``n_active`` vector still kills
        (and, on the fused kernel, DMA-dedupes) the slack probes between a
        query's count and the bucket width.  Pow-2 widths bound the jit
        cache at log2(nprobe) traces per plane, the same amortisation the
        coalesced serving plane's _BUCKET query padding uses.
        """
        g_total = stacked.index.routing.n_grains
        traffic = self._traffic_for(segments, g_total)
        hub_host = self._hub_mask_host(traffic)
        hub = jax.device_put(hub_host) if hub_host is not None else None
        pkw = {k: kw[k] for k in ("tag_mask", "ts_range") if k in kw}
        for k in ("tenant_live", "tenant_ix"):
            if k in kw:
                pkw[k] = kw[k]
        call_spans.stage(spans.DISPATCH)
        gids_d, na_d, wins, touches = planner.probe_plan(
            stacked, qj, nprobe=probe, probe_margin=probe_margin,
            min_probes=min_probes, hub_mask=hub, **pkw)
        # Explicit D2H of the plan: the host bucketing phase is the point.
        call_spans.stage(spans.READBACK)
        gids_h = np.asarray(jax.device_get(gids_d), np.int32)
        na_h = np.asarray(jax.device_get(na_d), np.int32)
        traffic["wins"] += np.asarray(jax.device_get(wins), np.int64)
        traffic["touches"] += np.asarray(jax.device_get(touches), np.int64)
        traffic["queries"] += int(na_h.shape[0])
        traffic["active_probes"] += int(na_h.sum())

        cap = stacked.index.grains.cap
        q_n = q.shape[0]
        cold = mode == "B" and stacked.index.raw is None
        if cold:
            pe = (pool_eff if budgets is None
                  else min(pool_eff, int(budgets[1])))
            out_ids = np.full((q_n, pe), -1, np.int64)
            out_d = np.full((q_n, pe), _BIG, np.float32)
        else:
            out_ids = np.full((q_n, topk_eff), -1, np.int64)
            out_d = np.full((q_n, topk_eff), _BIG, np.float32)

        wq = np.ones_like(na_h)                  # pow-2 bucket widths
        while bool((wq < na_h).any()):
            wq = np.where(wq < na_h, wq * 2, wq)
        wq = np.minimum(wq, probe)
        for w in sorted(int(v) for v in np.unique(wq)):
            call_spans.stage(spans.DISPATCH)
            sel = np.nonzero(wq == w)[0]
            # clamp the pool to what w grains can hold: a narrow bucket
            # must not ask top-k for more slots than it scans
            pool_b = min(pool_eff, w * cap)
            topk_b = min(topk_eff, pool_b)
            bkw = dict(kw, nprobe=w)
            if tenant_ix_host is not None:
                bkw["tenant_ix"] = jax.device_put(tenant_ix_host[sel])
            plan = (jax.device_put(np.ascontiguousarray(gids_h[sel, :w])),
                    jax.device_put(np.minimum(na_h[sel], w)))
            qb = jnp.asarray(q[sel])
            if cold:
                pe_b = min(pe, pool_b)
                res = planner.search_stacked(
                    stacked, qb, pool=pool_b, topk=pe_b, mode="A",
                    translate=False, probe_plan=plan, **bkw)
                call_spans.stage(spans.READBACK)
                out_ids[sel[:, None], np.arange(pe_b)[None, :]] = \
                    jax.device_get(res.ids)
                out_d[sel[:, None], np.arange(pe_b)[None, :]] = \
                    jax.device_get(res.dists)
            else:
                res = planner.search_stacked(
                    stacked, qb, pool=pool_b, topk=topk_b, mode=mode,
                    probe_plan=plan, **bkw)
                call_spans.stage(spans.READBACK)
                out_ids[sel[:, None], np.arange(topk_b)[None, :]] = \
                    np.asarray(jax.device_get(res.ids), np.int64)
                out_d[sel[:, None], np.arange(topk_b)[None, :]] = \
                    jax.device_get(res.dists)
        if cold:
            ok = (out_ids >= 0) & (out_d < _BIG / 2)
            call_spans.stage(spans.COLD_RERANK)
            return self._cold_rerank(q, segments, entry["offsets"],
                                     entry["gids"], out_ids, ok, topk_eff)
        return out_ids, out_d

    def _cold_rerank(self, q, segments, offsets, gids_host, rows, ok, topk):
        """Host-side exact Mode B re-rank of a merged candidate pool from
        the per-segment cold memmaps.  ``rows`` are original flat rows of
        the concatenated raw tier (slots with ok=False are ignored)."""
        rows_c = np.maximum(rows, 0)
        seg_idx = np.searchsorted(offsets, rows_c, side="right") - 1
        local = rows_c - offsets[seg_idx]
        cand = np.zeros(rows.shape + (q.shape[1],), np.float32)
        for si, seg in enumerate(segments):
            m = ok & (seg_idx == si)
            if m.any():
                cand[m] = seg.raw_vectors()[local[m]]
        exact = np.sum((cand - q[:, None, :]) ** 2, axis=-1)
        exact = np.where(ok, exact, _BIG)
        order = np.argsort(exact, axis=1)[:, :topk]
        ids = np.where(ok, gids_host[rows_c], -1)
        return (np.take_along_axis(ids, order, axis=1),
                np.take_along_axis(exact, order, axis=1))

    def _sharded_statics(self, plane: ShardedStackedSegments, n_shards: int,
                         topk: int, nprobe: Optional[int],
                         pool: Optional[int]):
        """Per-shard jit-static knobs, clamped to the local grain slice."""
        g_local = plane.index.grains.n_grains // n_shards
        cap = plane.index.grains.cap
        probe = max(1, min(nprobe if nprobe is not None else self.cfg.nprobe,
                           g_local))
        want_pool = pool if pool is not None else self.cfg.pool
        pool_eff = min(max(want_pool, topk), probe * cap)
        return probe, pool_eff

    def _batch_axis(self, mesh, grain_axis: str, shard_queries: bool,
                    q_n: int) -> Optional[str]:
        """Pick the query-batch mesh axis, or None to replicate queries.
        An unsatisfiable explicit request is an error, not a silent
        replicated fallback."""
        if not shard_queries:
            return None
        other = [a for a in mesh.axis_names if a != grain_axis]
        if not other or mesh.shape[other[0]] <= 1:
            raise ValueError(
                f"shard_queries=True needs a >1-sized mesh axis besides "
                f"{grain_axis!r}; mesh has {dict(mesh.shape)}")
        if q_n % mesh.shape[other[0]] != 0:
            raise ValueError(
                f"shard_queries=True needs the {other[0]!r} axis size "
                f"({mesh.shape[other[0]]}) to divide the query count "
                f"({q_n}); pad the batch to a multiple of the axis")
        return other[0]

    def _search_segments_sharded(self, q, man, *, topk, mode, tag_mask,
                                 ts_range, scan_impl, nprobe, pool, mesh,
                                 grain_axis, shard_queries, now,
                                 budgets=None, tenant_live=None,
                                 tenant_ix=None, adaptive=False,
                                 probe_margin=1.0, min_probes=1,
                                 call_spans=spans.UNTRACED):
        """Distributed fused search: shard-local route/scan/pool/re-rank and
        one all-gather merge collective.  Returns numpy (global_ids, dists).

        tenant_live/tenant_ix: as in :meth:`_search_segments_fused`; the
        [T, G, cap] stack is placed grain-sharded on dim 1 (tenant axis
        replicated) so each shard sees its slice of every tenant's bitmap.

        adaptive: the stopping rule runs IN-JIT per shard (each shard's
        probe budget shrinks independently against its local routing
        table) — no host bucketing, the shard_map body stays one
        fixed-shape program with killed probes masked/DMA-deduped in
        place.  Hub pinning is a single-device serving feature: the
        traffic counters accumulate on the fused plane's grain axis,
        which does not map onto the sharded plane's permuted layout, so
        the sharded path passes no hub mask (the planner-level hub_mask
        hook stays available to callers that shard their own counters).
        """
        from ..distributed import sharding as shd
        segments = man.segments
        entry = self._sharded_for(segments, mesh, grain_axis, scan_impl)
        plane = self._live_plane(entry, man, now)
        perm, offsets, gids_host = (entry["perm"], entry["offsets"],
                                    entry["gids"])
        n_shards = mesh.shape[grain_axis]
        probe, pool_eff = self._sharded_statics(plane, n_shards, topk,
                                                nprobe, pool)
        qeff = index_mod.int32_safe_qmax(self.cfg.k, self.cfg.coord_bits)
        # Explicit placement, as in _search_segments_fused: no implicit H2D
        # of the filter scalars under the sanitizer's transfer guard.
        tm = (jax.device_put(np.uint32(tag_mask))
              if tag_mask is not None else None)
        tr = ((jax.device_put(np.float32(ts_range[0])),
               jax.device_put(np.float32(ts_range[1])))
              if ts_range is not None else None)
        kw = dict(mesh=mesh, grain_axis=grain_axis,
                  batch_axis=self._batch_axis(mesh, grain_axis,
                                              shard_queries, q.shape[0]),
                  nprobe=probe, envelope_frac=self.cfg.envelope_frac,
                  qeff=qeff, scan_impl=scan_impl, budgets=budgets,
                  tag_mask=tm, ts_range=tr)
        if adaptive and not math.isinf(probe_margin):
            kw["probe_margin"] = probe_margin
            kw["min_probes"] = min_probes
        if tenant_live is not None:
            kw["tenant_live"] = shd.shard_plane_field(
                np.asarray(tenant_live), entry["rules"], "tenant_live",
                dim=1)
            kw["tenant_ix"] = jax.device_put(np.asarray(tenant_ix, np.int32))
        qj = jnp.asarray(q)

        if mode == "B" and plane.index.raw is None:
            # Cold tier: sharded approximate scan, merged union of the
            # per-shard pools (topk = n_shards * pool keeps every shard's
            # pool in the gathered result), host re-rank from the memmaps
            # after translating permuted rows back to original flat rows.
            # Stage budgets cap each shard's useful pool at b2.
            pe = (pool_eff if budgets is None
                  else min(pool_eff, int(budgets[1])))
            call_spans.stage(spans.DISPATCH)
            res = planner.search_stacked_sharded(
                plane, qj, pool=pe, topk=n_shards * pe,
                mode="A", translate=False, **kw)
            call_spans.stage(spans.READBACK)
            rows_perm = jax.device_get(res.ids)
            ok = (rows_perm >= 0) & (jax.device_get(res.dists) < BIG / 2)
            rows = np.where(ok, perm[np.maximum(rows_perm, 0)], -1)
            ok &= rows >= 0
            call_spans.stage(spans.COLD_RERANK)
            return self._cold_rerank(q, segments, offsets, gids_host,
                                     rows, ok, min(topk, rows.shape[1]))

        call_spans.stage(spans.DISPATCH)
        res = planner.search_stacked_sharded(plane, qj, pool=pool_eff,
                                             topk=topk, mode=mode, **kw)
        # Explicit D2H: the one sanctioned device->host hop of the warm
        # tier (the final top-k), visible to the transfer guard as such.
        call_spans.stage(spans.READBACK)
        return (np.asarray(jax.device_get(res.ids), np.int64),
                np.asarray(jax.device_get(res.dists), np.float32))

    def _search_memtable(self, q, man: Manifest, topk, tag_mask, ts_range,
                         now):
        """Hot tail: exact scan (the paper's unsealed memtable semantics).

        Reads the manifest's *captured* rows, never the live memtable — a
        seal() after snapshot() must not change what the snapshot returns.
        Liveness (tombstones / upsert shadowing / TTL) is applied with the
        manifest's captured mutation table, like every sealed plane.
        """
        if man.mem_n <= 0:
            return None, None
        mem = np.stack(man.mem[:man.mem_n])
        keep = np.ones(man.mem_n, bool)
        if man.mem_ids:
            gids = np.asarray(man.mem_ids[:man.mem_n], np.int64)
        else:                      # legacy manifest: contiguous gid run
            gids = man.mem_base + np.arange(man.mem_n, dtype=np.int64)
        seqs = (np.asarray(man.mem_seq[:man.mem_n], np.int64)
                if man.mem_seq else gids)
        lv = _live_rows(man.mut_gid, man.mut_seq, gids, seqs)
        if lv is not None:
            keep &= lv
        if man.mem_expire:
            keep &= np.asarray(man.mem_expire[:man.mem_n],
                               np.float64) > now
        if tag_mask is not None:
            keep &= (np.asarray(man.mem_tags[:man.mem_n], np.uint32)
                     & np.uint32(tag_mask)) != 0
        if ts_range is not None:
            tsv = np.asarray(man.mem_ts[:man.mem_n], np.float32)
            keep &= (tsv >= ts_range[0]) & (tsv < ts_range[1])
        # mask *before* top-k so filtered-out rows cannot shadow valid ones
        d_all = np.sum((mem[None, :, :] - q[:, None, :]) ** 2, axis=-1)
        d_all = np.where(keep[None, :], d_all, _BIG)
        kk = min(topk, man.mem_n)
        order = np.argsort(d_all, axis=1)[:, :kk]
        return (gids[order],
                np.take_along_axis(d_all, order, axis=1))

    # --------------------------------------------------- legacy looped path
    def _seg_live_mask(self, man: Manifest, seg: Segment,
                       now) -> Optional[np.ndarray]:
        """[G, cap] liveness bitmap of ONE segment's grain panels (the
        looped oracle's per-segment equivalent of the stacked live leaf)."""
        lv = _live_rows(man.mut_gid, man.mut_seq,
                        seg.global_ids(), seg.global_seqs())
        if seg.expire is not None:
            alive_t = seg.expire > now
            if not alive_t.all():
                lv = alive_t if lv is None else lv & alive_t
        if lv is None:
            return None
        ids = np.asarray(seg.index.grains.ids)      # local rows, -1 padding
        return (ids >= 0) & lv[np.maximum(ids, 0)]

    def _search_looped(self, q, man: Manifest, *, topk, mode, tag_mask,
                       ts_range, scan_impl, now,
                       call_spans=spans.UNTRACED) -> SearchResult:
        """Per-segment Python-loop search (pre-fusion data plane).

        Kept as the parity oracle for `search` and the baseline for
        benchmarks/segment_scale.py: one jit dispatch + host sync per
        segment, per-segment top-k merged by a host argsort.
        """
        all_ids, all_d = [], []
        for seg in man.segments:
            call_spans.stage(spans.DISPATCH)
            extra = None
            g = seg.index.grains
            live = self._seg_live_mask(man, seg, now)
            if tag_mask is not None or ts_range is not None \
                    or live is not None:
                keep = jnp.ones(g.ids.shape, bool) if live is None \
                    else jnp.asarray(live)
                if tag_mask is not None and g.tags is not None:
                    keep &= (g.tags & jnp.uint32(tag_mask)) != 0
                if ts_range is not None and g.ts is not None:
                    lo, hi = ts_range
                    keep &= (g.ts >= lo) & (g.ts < hi)
                extra = keep
            if mode == "B" and seg.index.raw is None:
                # cold tier: approximate scan in-core, exact re-rank via mmap
                res = index_mod.search(seg.index, q, self.cfg, topk=max(
                    topk, self.cfg.pool), mode="A", scan_impl=scan_impl,
                    extra_mask=extra)
                call_spans.stage(spans.READBACK)
                raw = seg.raw_vectors()
                cand = np.asarray(res.ids)
                # candidates pruned in-scan (validity / mixed-recall mask) come
                # back with approx dist = BIG; keep them pruned through re-rank
                cand_ok = (cand >= 0) & (np.asarray(res.dists) < BIG / 2)
                call_spans.stage(spans.COLD_RERANK)
                exact = np.sum(
                    (raw[np.maximum(cand, 0)] - q[:, None, :]) ** 2, axis=-1)
                exact = np.where(cand_ok, exact, _BIG)
                order = np.argsort(exact, axis=1)[:, :topk]
                ids = np.take_along_axis(cand, order, axis=1)
                d = np.take_along_axis(exact, order, axis=1)
            else:
                res = index_mod.search(seg.index, q, self.cfg, topk=topk,
                                       mode=mode, scan_impl=scan_impl,
                                       extra_mask=extra)
                call_spans.stage(spans.READBACK)
                ids, d = np.asarray(res.ids), np.asarray(res.dists)
            all_ids.append(seg.map_local(ids))
            all_d.append(d)
        call_spans.stage(spans.FINALIZE)
        return self._merge_with_memtable(q, man, all_ids, all_d, topk,
                                         tag_mask, ts_range, now)
