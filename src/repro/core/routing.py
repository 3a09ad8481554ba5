"""Level-1 hierarchical centroid routing (paper §2.3).

For a query batch Q we compute ambient-space distances to all G grain
centroids and keep the top-P (nprobe).  Empty grains are never selected.

For a :class:`~repro.core.types.StackedSegments` super-index the same
routine routes over the *concatenated* routing plane of every sealed
segment at once (global top-P); ``route_per_segment`` instead reproduces
the legacy per-segment-loop semantics (top-P within each segment) inside
one fused call, which the parity tests rely on.

``grain_mask`` implements mixed-recall *filter pushdown*: grains without a
single record matching the tag/ts predicate are excluded from routing, so
probes are never wasted on segments the filter rules out entirely.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from .spans import ROUTE
from .types import BIG, RoutingPlane


def _centroid_d2(plane: RoutingPlane, q: jax.Array,
                 grain_mask: Optional[jax.Array]) -> jax.Array:
    """Masked query->centroid distances.  q [Q, d] -> d2 [Q, G]."""
    c2 = jnp.sum(plane.centroids * plane.centroids, axis=-1)      # [G]
    q2 = jnp.sum(q * q, axis=-1, keepdims=True)                   # [Q, 1]
    d2 = q2 - 2.0 * (q @ plane.centroids.T) + c2[None, :]         # [Q, G]
    ok = plane.sizes > 0
    if grain_mask is not None:
        # [G] shared pushdown, or [Q, G] per-query (tenant visibility)
        ok = jnp.logical_and(ok, grain_mask)
    if ok.ndim == 1:
        ok = ok[None, :]
    return jnp.where(ok, d2, BIG)


@jax.named_scope(ROUTE)
def route(plane: RoutingPlane, q: jax.Array, nprobe: int,
          grain_mask: Optional[jax.Array] = None):
    """Select the top-P closest grains per query.

    q: [Q, d].  grain_mask: optional [G] bool — additional grain validity
    (filter pushdown) — or [Q, G] bool for *per-query* pushdown (each
    query routes only over the grains its tenant can see).
    Returns (grain_ids [Q, P] i32, grain_d2 [Q, P] f32).
    """
    d2 = _centroid_d2(plane, q, grain_mask)
    neg_d, idx = jax.lax.top_k(-d2, nprobe)
    return idx.astype(jnp.int32), -neg_d


def check_probe_args(adaptive: bool, probe_margin, min_probes=None) -> None:
    """Host-side validation of the adaptive-probing knobs.

    Shared by ``VectorStore.search``, the serving engine, the tenancy
    coalescer and the launcher, so a bad combination fails at submit time
    with one actionable message instead of as a shape/trace error three
    layers down the jitted dispatch (the ``check_budgets`` discipline).
    """
    if probe_margin is not None:
        if not adaptive:
            raise ValueError(
                "probe_margin= only applies to adaptive routing; pass "
                "adaptive=True (or drop probe_margin)")
        m = float(probe_margin)
        if math.isnan(m) or m < 0.0:
            raise ValueError(
                f"probe_margin must be a float >= 0 (inf = exhaustive, "
                f"i.e. static nprobe), got {probe_margin!r}")
    if min_probes is not None and (isinstance(min_probes, bool)
                                   or not isinstance(min_probes, int)
                                   or min_probes < 1):
        raise ValueError(
            f"min_probes must be an int >= 1, got {min_probes!r}")


@jax.named_scope(ROUTE)
def adaptive_prefix(gids: jax.Array, gd2: jax.Array, *, margin: float,
                    min_probes: int = 1,
                    hub_mask: Optional[jax.Array] = None):
    """Per-query early termination over the routed top-P (in-jit).

    The routing distance to a grain's centroid lower-bounds how useful the
    grain can be: a grain whose centroid is far beyond the query's best
    grain rarely contributes to the final pool (the SPANN closure rule).
    A probe p stays *active* iff

        gd2[q, p] <= (1 + margin) * gd2[q, 0]        (distance-gap rule)

    or it is one of the first ``min_probes`` probes (tail-recall floor),
    or it is a **hub** — a persistently high-traffic grain (``hub_mask``
    [G] bool, from the routing-win counters) that is always probed to
    stabilize tail recall.  Probes on invalid grains (``gd2 >= BIG/2`` —
    masked or empty) are always killed.

    Active probes are stable-partitioned to the FRONT of the probe axis
    (relative order preserved — ascending gd2 stays ascending), so the
    ragged-probe kernel consumes a plain per-query prefix length.

    Returns (gids [Q, P] i32 reordered, n_active [Q] i32 >= 1).
    ``margin=inf`` callers must shortcut before tracing (``(1 + inf) * 0``
    is NaN); the planner treats inf as "static nprobe" by construction.
    """
    p_n = gids.shape[1]
    pos = jnp.arange(p_n, dtype=jnp.int32)[None, :]
    lead = gd2[:, :1]                                 # best routing distance
    active = gd2 <= (1.0 + margin) * lead
    if hub_mask is not None:
        active = jnp.logical_or(active, hub_mask[gids])
    active = jnp.logical_and(active, gd2 < BIG / 2)
    active = jnp.logical_or(active, pos < min_probes)
    # stable partition: actives first, original (ascending-gd2) order kept
    order = jnp.argsort(jnp.logical_not(active), axis=1, stable=True)
    gids_s = jnp.take_along_axis(gids, order, axis=1)
    n_active = jnp.maximum(jnp.sum(active.astype(jnp.int32), axis=1), 1)
    return gids_s, n_active


def merge_target(centroids, live_counts, cap: int, src: int,
                 excluded=(), max_merged: Optional[int] = None) -> int:
    """Pick the grain an underfull grain ``src`` should merge into: the
    *nearest* other centroid whose group has room for src's live rows
    (combined count <= cap, and <= ``max_merged`` when given, so a merge
    never manufactures the overfull grain the next epoch would re-split).

    Host-side (numpy) — maintenance control plane.  ``excluded``: grain
    indices that may not be targets (retired/merged-away this epoch).
    Returns the target grain index, or -1 when no grain has room.
    """
    import numpy as np

    c = np.asarray(centroids, np.float32)
    cnt = np.asarray(live_counts, np.int64)
    d2 = np.sum((c - c[src]) ** 2, axis=1)
    d2[src] = np.inf
    for gi in excluded:
        d2[gi] = np.inf
    merged = cnt + cnt[src]
    limit = cap if max_merged is None else min(cap, max_merged)
    d2[(merged > limit) | (cnt == 0)] = np.inf
    best = int(np.argmin(d2))
    return best if np.isfinite(d2[best]) else -1


def rebuild_plane(centroids, sizes) -> RoutingPlane:
    """Assemble a routing plane from maintenance-final per-grain tables.

    The centroid table is the one structure whose *row count* tracks the
    grain count through split (grow), merge/retire (shrink) and refit
    (in-place recenter); maintenance funnels every rebuild through here so
    the invariant ``routing rows == grain panels`` has a single owner.
    Leaves are device arrays, like :func:`repro.core.index.build`'s plane.
    """
    import numpy as np

    c = np.asarray(centroids, np.float32)
    s = np.asarray(sizes, np.int32)
    assert c.shape[0] == s.shape[0], (c.shape, s.shape)
    return RoutingPlane(centroids=jnp.asarray(c), sizes=jnp.asarray(s))


@jax.named_scope(ROUTE)
def route_per_segment(plane: RoutingPlane, q: jax.Array, nprobe: int,
                      seg_shape: tuple,
                      grain_mask: Optional[jax.Array] = None):
    """Top-P routing *within each segment* of a stacked routing plane.

    plane holds S*G fused grains; seg_shape = (S, G) recovers the leading
    segment axis.  Returns (grain_ids [Q, S*P] i32 — indices into the fused
    [S*G] grain axis — and grain_d2 [Q, S*P] f32).  Matches the legacy
    per-segment Python loop's probe set exactly, in one call.
    """
    s, g = seg_shape
    d2 = _centroid_d2(plane, q, grain_mask)                       # [Q, S*G]
    d2 = d2.reshape(q.shape[0], s, g)
    neg_d, idx = jax.lax.top_k(-d2, min(nprobe, g))               # [Q, S, P]
    idx = idx + (jnp.arange(s, dtype=idx.dtype) * g)[None, :, None]
    return (idx.reshape(q.shape[0], -1).astype(jnp.int32),
            -neg_d.reshape(q.shape[0], -1))
