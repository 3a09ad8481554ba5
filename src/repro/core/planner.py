"""Dual-mode query planner (paper §2.1, §2.3).

Pipeline per query batch:
  (1) centroid routing (top-P grains),
  (2) per-grain tangent projection of the query + quantization envelope filter,
  (3) Block-SoA scan of surviving grains (reference jnp or Pallas kernel),
  (4) Mode A: top-k straight from approximate distances;
      Mode B: gather raw vectors for the C-pool and exact-f32 L2 re-rank.

Everything is fixed-shape and jit-compatible.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import quantize, routing, scan, scanplane
from .cascade import check_budgets
from .spans import PROJECT, RERANK
from .types import (BIG, HNTLIndex, RoutingPlane, SearchResult,
                    ShardedStackedSegments, StackedSegments)


def project_queries(index: HNTLIndex, q: jax.Array, gids: jax.Array):
    """Project each query into each probed grain's tangent frame.

    q [Q, d], gids [Q, P] -> dict of per-(query,grain) quantities.
    """
    g = index.grains
    mu = g.mu[gids]                          # [Q, P, d]
    basis = g.basis[gids]                    # [Q, P, d, k]
    vc = q[:, None, :] - mu                  # [Q, P, d]
    zq = jnp.einsum("qpd,qpdk->qpk", vc, basis)          # [Q, P, k]
    vc2 = jnp.sum(vc * vc, axis=-1)                       # [Q, P]
    zq2 = jnp.sum(zq * zq, axis=-1)
    out = {"zq": zq, "vc2": vc2}
    rq = vc2 - zq2                                        # ||e_q||^2 (W orthonormal)
    if g.sketch_basis is not None:
        sb = g.sketch_basis[gids]                         # [Q, P, d, s]
        sq = jnp.einsum("qpd,qpds->qps", vc, sb)
        rq = rq - jnp.sum(sq * sq, axis=-1)
        out["sq"] = sq
    out["rq"] = jnp.maximum(rq, 0.0)
    return out


def _gather_probed_panels(g, gids: jax.Array) -> dict:
    """THE per-query panel materialization the select planes eliminate:
    every probed grain's full panel is copied into a [Q, P, ...]-leading
    gather (``coords`` alone is [Q, P, k, cap]).  Kept as a named seam so
    benchmarks/tests can assert the fused path never reaches it."""
    return dict(coords=g.coords[gids], res=g.res[gids], valid=g.valid[gids],
                ids=g.ids[gids],
                sketch=g.sketch[gids] if g.sketch is not None else None)


@jax.named_scope(PROJECT)
def _project_quantized(index: HNTLIndex, q: jax.Array, gids: jax.Array,
                       envelope_frac: float, qeff: int):
    """Shared per-(query, probed grain) prep of both plane kinds: tangent
    projection, envelope verdict, and query-side quantization.

    Returns (zq [Q, P, k] i32, rq [Q, P] f32, keep [Q, P] bool,
             sq [Q, P, s] i32 | None).
    """
    g = index.grains
    proj = project_queries(index, q, gids)
    scale = g.scale[gids]                                 # [Q, P]
    # Mixed precision: each probed grain quantizes the query at ITS stored
    # width (qmaxg gather), so query coords live on the same integer lattice
    # as the panel they are scanned against.  Fixed-width planes keep the
    # static qeff.
    qm = qeff if g.qmaxg is None else g.qmaxg[gids][..., None]
    # Envelope filter: prune structurally-incompatible grains (paper §2.3).
    keep = quantize.envelope_keep(proj["zq"], scale[..., None], envelope_frac,
                                  qmax=qm)                # [Q, P]
    zq_q = quantize.quantize_coords(proj["zq"], scale[..., None],
                                    qmax=qm).astype(jnp.int32)
    sq = None
    if g.sketch_basis is not None:
        sk_scale = g.sketch_scale[gids]
        sq = quantize.quantize_coords(proj["sq"], sk_scale[..., None],
                                      qmax=127).astype(jnp.int32)
    return zq_q, proj["rq"], keep, sq


def scan_probed(index: HNTLIndex, q: jax.Array, gids: jax.Array,
                envelope_frac: float, qeff: int,
                scan_fn=None,
                extra_mask: Optional[jax.Array] = None,
                tenant_mask: Optional[jax.Array] = None,
                tenant_ix: Optional[jax.Array] = None,
                n_active: Optional[jax.Array] = None):
    """Gather-plane stages (2)+(3): project, envelope-filter, Block-SoA scan
    over per-query *copies* of the probed panels.

    Returns (dists [Q, P*cap] f32, ids [Q, P*cap] i32).
    scan_fn: callable with `scan.blocksoa_scan`'s signature (Pallas or ref).
    extra_mask: [G, cap] bool mixed-recall predicate evaluated in-situ.
    tenant_mask [T, G, cap] + tenant_ix [Q]: per-query tenant visibility —
    gather planes fold it into the per-query extra mask (the gather is
    probed-panels-only, [Q, P, cap], never the full [T, G, cap] stack).
    n_active [Q] i32 (adaptive routing): gather planes have no ragged DMA
    to dedupe, so killed probes simply fold into the envelope verdict.
    """
    g = index.grains
    zq_q, rq, keep, sq = _project_quantized(index, q, gids, envelope_frac,
                                            qeff)
    if n_active is not None:
        keep = jnp.logical_and(
            keep, jnp.arange(gids.shape[1], dtype=jnp.int32)[None, :]
            < n_active[:, None])
    scale = g.scale[gids]                                 # [Q, P]
    res_scale = g.res_scale[gids]
    panels = _gather_probed_panels(g, gids)

    kw = {}
    if g.sketch_basis is not None:
        kw = dict(sq=sq, sketch=panels["sketch"],
                  sketch_scale=g.sketch_scale[gids])
    if extra_mask is not None:
        kw["extra_mask"] = extra_mask[gids]
    if tenant_mask is not None:
        tq = tenant_mask[tenant_ix[:, None], gids]        # [Q, P, cap]
        kw["extra_mask"] = tq if "extra_mask" not in kw \
            else jnp.logical_and(kw["extra_mask"], tq)

    fn = scan_fn if scan_fn is not None else scan.blocksoa_scan
    dists = jax.vmap(fn)(zq_q, rq, panels["coords"], panels["res"],
                         panels["valid"], scale, res_scale, **kw)
    # kill pruned grains wholesale
    dists = jnp.where(keep[..., None], dists, BIG)        # [Q, P, cap]
    qn = q.shape[0]
    return dists.reshape(qn, -1), panels["ids"].reshape(qn, -1)


def select_probed(index: HNTLIndex, q: jax.Array, gids: jax.Array,
                  envelope_frac: float, qeff: int, *, width: int, runner,
                  budgets: Optional[tuple] = None,
                  extra_mask: Optional[jax.Array] = None,
                  tenant_mask: Optional[jax.Array] = None,
                  tenant_ix: Optional[jax.Array] = None,
                  n_active: Optional[jax.Array] = None):
    """Select-plane stages (2)+(3)+(first-stage top-k): project, then hand
    the STACKED panel tier (no per-query gather) to a streaming scan→select
    runner that emits only the running top-``width`` pool.

    Returns (dists [Q, width] f32 ascending, rows [Q, width] i32).
    tenant_mask/tenant_ix ride through to the runner untouched — select
    runners stream the per-tenant visibility table (second scalar-prefetch
    stream in the fused kernel) instead of gathering per-query masks.
    n_active [Q] i32 (adaptive routing) rides through the same way — the
    runner's ragged-probe stream (third scalar-prefetch in the kernel).
    """
    g = index.grains
    zq_q, rq, keep, sq = _project_quantized(index, q, gids, envelope_frac,
                                            qeff)
    mask = g.valid if extra_mask is None \
        else jnp.logical_and(g.valid, extra_mask)         # [G, cap]
    kw = {}
    if g.sketch_basis is not None:
        kw = dict(sq=sq, sketch=g.sketch, sketch_scale=g.sketch_scale)
    if tenant_mask is not None:
        kw.update(tenant_mask=tenant_mask, tenant_ix=tenant_ix)
    if budgets is not None:
        kw["budgets"] = budgets
    if n_active is not None:
        kw["n_active"] = n_active
    width = min(width, gids.shape[1] * g.cap)
    return runner(gids, zq_q, rq, keep, g.coords, g.res, mask, g.ids,
                  g.scale, g.res_scale, width=width, **kw)


def candidate_stage(index: HNTLIndex, q: jax.Array, gids: jax.Array, *,
                    envelope_frac: float, qeff: int, width: int,
                    scan_impl: Optional[str] = None,
                    budgets: Optional[tuple] = None,
                    extra_mask: Optional[jax.Array] = None,
                    tenant_mask: Optional[jax.Array] = None,
                    tenant_ix: Optional[jax.Array] = None,
                    n_active: Optional[jax.Array] = None):
    """Dispatch the candidate-generation stage to a ScanPlane backend.

    Gather backends return the full [Q, P*cap] slot matrix; select backends
    return the two-stage-selected [Q, min(width, P*cap)] pool.  Either shape
    feeds :func:`_candidate_epilogue` unchanged (it tops-k whatever it
    gets), so the epilogue arithmetic — and with it the fused/sharded parity
    contract — is backend-independent.  tenant_mask [T, G, cap] +
    tenant_ix [Q] (multi-tenant serving) are boolean per-query visibility:
    every backend applies them as a pure AND with its existing masks, so
    backend parity is tenant-independent too.  n_active [Q] i32 (adaptive
    routing's ragged-probe vector): select backends with the ``adaptive``
    registry flag consume it natively (kernel prefetch stream), gather
    backends fold it into the envelope verdict — same kill semantics.
    """
    plane = scanplane.get_scan_plane(scan_impl)
    if budgets is not None and not plane.staged:
        raise ValueError(
            f"scan plane {plane.name!r} is not staged; per-stage survivor "
            "budgets need a cascade backend (scan_impl='cascade')")
    if plane.kind == scanplane.SELECT:
        if n_active is not None and not plane.adaptive:
            raise ValueError(
                f"scan plane {plane.name!r} does not accept the "
                "ragged-probe vector (n_active=); register it with "
                "adaptive=True or use a non-adaptive dispatch")
        return select_probed(index, q, gids, envelope_frac, qeff,
                             width=width, runner=plane.runner,
                             budgets=budgets if plane.staged else None,
                             extra_mask=extra_mask, tenant_mask=tenant_mask,
                             tenant_ix=tenant_ix, n_active=n_active)
    return scan_probed(index, q, gids, envelope_frac, qeff,
                       scan_fn=plane.runner, extra_mask=extra_mask,
                       tenant_mask=tenant_mask, tenant_ix=tenant_ix,
                       n_active=n_active)


@functools.partial(
    jax.jit,
    static_argnames=("nprobe", "pool", "topk", "mode", "envelope_frac",
                     "qeff", "scan_impl", "budgets"))
def search(index: HNTLIndex, q: jax.Array, *, nprobe: int, pool: int,
           topk: int, mode: str = "B", envelope_frac: float = 0.25,
           qeff: int = 8191, scan_impl: Optional[str] = None,
           budgets: Optional[tuple] = None,
           extra_mask: Optional[jax.Array] = None) -> SearchResult:
    """Full HNTL search.  mode='A' self-contained, mode='B' tiered re-rank.

    scan_impl: ScanPlane backend name (see ``core.scanplane``); None=auto.
    budgets: (b1, b2) per-stage survivor budgets for cascade backends.
    Pruned result slots (filtered, padding, pool exhausted) return id -1 —
    the same ``dist >= BIG / 2`` convention as the stacked planes.
    """
    check_budgets(budgets, topk)
    gids, _ = routing.route(index.routing, q, nprobe)
    dists, ids = candidate_stage(
        index, q, gids, envelope_frac=envelope_frac, qeff=qeff,
        width=min(max(pool, topk), nprobe * index.grains.cap),
        scan_impl=scan_impl, budgets=budgets, extra_mask=extra_mask)

    # Mode B: candidate pool C -> exact f32 L2 re-rank over the raw tier
    assert mode == "A" or index.raw is not None, \
        "Mode B needs the raw (cold) tier"
    return _candidate_epilogue(
        dists, ids, q, index.raw, pool=pool, topk=topk, mode=mode,
        translate=lambda i, d: jnp.where(d < BIG / 2, i, -1))


# ---------------------------------------------------------------------------
# Fused multi-segment search (the LSM store's data plane)
# ---------------------------------------------------------------------------


def _mixed_recall_mask(grains, tag_mask, ts_range, live=None):
    """In-jit [G, cap] predicate + [G] routing pushdown from tag/ts filters
    and the mutation-epoch liveness bitmap.

    Returns (extra_mask | None, grain_ok | None).  grain_ok excludes grains
    with *zero* matching records from routing, so top-P probes are never
    spent on segments the filter rules out wholesale (or on fully-dead
    grains).  ``live`` is the per-slot tombstone/TTL mask pushed in from the
    store — it rides the same in-situ predicate path as tag/ts, so deletes
    are visible inside the one-dispatch scan without re-stacking.
    """
    if tag_mask is None and ts_range is None and live is None:
        return None, None
    keep = grains.valid
    if live is not None:
        keep = jnp.logical_and(keep, live)
    if tag_mask is not None and grains.tags is not None:
        keep = jnp.logical_and(
            keep, (grains.tags & tag_mask.astype(jnp.uint32)) != 0)
    if ts_range is not None and grains.ts is not None:
        lo, hi = ts_range
        keep = jnp.logical_and(keep, (grains.ts >= lo) & (grains.ts < hi))
    return keep, jnp.any(keep, axis=1)


def _tenant_grain_mask(grains, extra, grain_ok, tenant_live, tenant_ix):
    """Per-query routing pushdown for tenant visibility.

    A grain is probe-worthy for query q iff its tenant can see at least one
    slot that also passes the shared predicate — [T, G, cap] any-reduced to
    [T, G] once, then gathered per query.  Combined with the shared [G]
    pushdown; returns a [Q, G] mask (or the unchanged shared one)."""
    if tenant_live is None:
        return grain_ok
    base = extra if extra is not None else grains.valid
    ok_q = jnp.any(jnp.logical_and(tenant_live, base[None]),
                   axis=2)[tenant_ix]                     # [Q, G]
    return ok_q if grain_ok is None else jnp.logical_and(grain_ok, ok_q)


def _translate_rows(stacked: StackedSegments, rows: jax.Array,
                    dists: jax.Array) -> jax.Array:
    """Flat raw rows -> global vector ids (-1 for padding / pruned slots)."""
    ok = jnp.logical_and(rows >= 0, dists < BIG / 2)
    gid = stacked.gid_of_row[jnp.maximum(rows, 0)]
    return jnp.where(ok, gid, jnp.int32(-1))


@jax.named_scope(RERANK)
def _candidate_epilogue(dists, rows, q, raw, *, pool: int, topk: int,
                        mode: str, translate):
    """Shared Mode A/B tail of every plane (single index, fused, sharded):
    candidate pool -> (Mode B) exact f32 re-rank -> top-k -> id translation.

    ``translate``: fn(rows, dists) -> ids.  Both planes must keep using this
    one epilogue — the bit-for-bit parity contract between them depends on
    the pooling/re-rank arithmetic staying identical.
    """
    if mode == "A":
        neg_d, pos = jax.lax.top_k(-dists, topk)
        rows_k = jnp.take_along_axis(rows, pos, axis=1)
        d_k = -neg_d
    else:
        neg_d, pos = jax.lax.top_k(-dists, pool)              # [Q, C]
        cand_rows = jnp.take_along_axis(rows, pos, axis=1)
        cand_ok = neg_d > -BIG / 2
        cand = raw[jnp.maximum(cand_rows, 0)]                 # [Q, C, d]
        exact = jnp.sum((cand - q[:, None, :]) ** 2, axis=-1)
        exact = jnp.where(cand_ok, exact, BIG)
        neg_e, pos_e = jax.lax.top_k(-exact, topk)
        rows_k = jnp.take_along_axis(cand_rows, pos_e, axis=1)
        d_k = -neg_e
    return SearchResult(ids=translate(rows_k, d_k), dists=d_k)


@functools.partial(
    jax.jit,
    static_argnames=("nprobe", "pool", "topk", "mode", "envelope_frac",
                     "qeff", "scan_impl", "budgets", "route_mode",
                     "seg_shape", "translate", "probe_margin", "min_probes"))
def search_stacked(stacked: StackedSegments, q: jax.Array, *, nprobe: int,
                   pool: int, topk: int, mode: str = "B",
                   envelope_frac: float = 0.25, qeff: int = 8191,
                   scan_impl: Optional[str] = None,
                   budgets: Optional[tuple] = None,
                   route_mode: str = "global",
                   seg_shape: Optional[tuple] = None, translate: bool = True,
                   tag_mask: Optional[jax.Array] = None,
                   ts_range: Optional[tuple] = None,
                   tenant_live: Optional[jax.Array] = None,
                   tenant_ix: Optional[jax.Array] = None,
                   probe_margin: Optional[float] = None,
                   min_probes: int = 1,
                   hub_mask: Optional[jax.Array] = None,
                   probe_plan: Optional[tuple] = None) -> SearchResult:
    """Fused HNTL search across *all* sealed segments in one dispatch.

    Replaces the per-segment Python loop: one global routing pass over the
    concatenated [S*G] routing plane, one vmapped Block-SoA scan over the
    surviving grains, one merged candidate pool, one Mode-B exact re-rank.

    scan_impl: ScanPlane backend for the candidate stage (see
      ``core.scanplane``) — gather backends materialize [Q, P*cap] slot
      state, select backends ("fused"/"fused_ref") stream panels and emit
      only [Q, pool].  None = "auto".
    route_mode: "global" — top-P over every segment's grains at once (work
      independent of segment count, the production path); "per_segment" —
      top-P within each segment (legacy loop semantics; needs seg_shape).
    translate: map flat raw rows to global ids in-jit.  The cold-tier path
      sets translate=False and resolves rows -> (segment, local) on the host.
    tag_mask / ts_range: *traced* mixed-recall predicates evaluated in-situ
      (and pushed down into routing), so filtered search is still one call.
    ``stacked.live`` (tombstone/upsert/TTL liveness) joins the same in-situ
    predicate, so mutated stores stay a single dispatch too.
    tenant_live [T, G, cap] + tenant_ix [Q] (multi-tenant coalesced
    serving): per-QUERY visibility over one shared plane — each query scans
    only its tenant's rows, with per-query routing pushdown, in the same
    single dispatch.
    probe_margin (static float) + min_probes + hub_mask [G] bool (adaptive
    routing, in-jit): after routing, the ``routing.adaptive_prefix``
    stopping rule kills probes beyond the distance-gap closure (hubs are
    always probed) and the ragged-probe vector rides to the candidate
    stage.  ``probe_margin=None`` is exactly today's static trace;
    ``probe_margin=inf`` is shortcut BEFORE tracing to the identical static
    path — bit-identity by construction, never by accident of arithmetic.
    probe_plan: precomputed (gids [Q, P], n_active [Q]) pair (from
    :func:`probe_plan`) that skips internal routing entirely — the store's
    bucketed adaptive dispatch slices one plan across width buckets.
    """
    check_budgets(budgets, topk)
    adaptive = probe_margin is not None and not math.isinf(probe_margin)
    index = stacked.index
    extra, grain_ok = _mixed_recall_mask(index.grains, tag_mask, ts_range,
                                         live=stacked.live)
    n_active = None
    if probe_plan is not None:
        assert route_mode != "per_segment", \
            "probe_plan needs global routing (one fused grain axis)"
        gids, n_active = probe_plan
    elif route_mode == "per_segment":
        # no filter pushdown here: the legacy loop routes unmasked and only
        # filters in-scan, and this mode's contract is loop-identical probes
        assert seg_shape is not None, "per_segment routing needs seg_shape"
        assert tenant_live is None, \
            "tenant visibility needs global routing (per-query pushdown)"
        assert not adaptive, \
            "adaptive routing needs global routing (route_mode='global')"
        gids, _ = routing.route_per_segment(index.routing, q, nprobe,
                                            seg_shape)
    else:
        gmask = _tenant_grain_mask(index.grains, extra, grain_ok,
                                   tenant_live, tenant_ix)
        gids, gd2 = routing.route(index.routing, q, nprobe, grain_mask=gmask)
        if adaptive:
            gids, n_active = routing.adaptive_prefix(
                gids, gd2, margin=probe_margin, min_probes=min_probes,
                hub_mask=hub_mask)
    dists, rows = candidate_stage(
        index, q, gids, envelope_frac=envelope_frac, qeff=qeff,
        width=max(pool, topk), scan_impl=scan_impl, budgets=budgets,
        extra_mask=extra, tenant_mask=tenant_live, tenant_ix=tenant_ix,
        n_active=n_active)

    # Mode B: merged candidate pool -> exact f32 re-rank over the fused
    # warm tier (single gather into the concatenated raw array).
    assert mode == "A" or index.raw is not None, \
        "in-jit Mode B needs the fused warm tier; cold stores re-rank on host"
    return _candidate_epilogue(
        dists, rows, q, index.raw, pool=pool, topk=topk, mode=mode,
        translate=(lambda r, d: _translate_rows(stacked, r, d)) if translate
        else (lambda r, d: r))


@functools.partial(jax.jit, static_argnames=("nprobe",))
def static_route(plane: RoutingPlane, q: jax.Array, *, nprobe: int,
                 grain_mask: Optional[jax.Array] = None):
    """:func:`probe_plan`'s ``margin=inf`` routing stage alone, over just
    the routing sub-tree: same ``routing.route`` call, so the gids are
    bit-identical, but the dispatch skips the full stacked-plane pytree
    and the traffic scatters (``n_active`` is the constant P and
    wins/touches are plain integer bincounts — the tiered path derives
    them on the host from the gids it reads back anyway)."""
    return routing.route(plane, q, nprobe, grain_mask=grain_mask)


@functools.partial(
    jax.jit,
    static_argnames=("nprobe", "probe_margin", "min_probes"))
def probe_plan(stacked: StackedSegments, q: jax.Array, *, nprobe: int,
               probe_margin: float, min_probes: int = 1,
               hub_mask: Optional[jax.Array] = None,
               tag_mask: Optional[jax.Array] = None,
               ts_range: Optional[tuple] = None,
               tenant_live: Optional[jax.Array] = None,
               tenant_ix: Optional[jax.Array] = None,
               grain_mask: Optional[jax.Array] = None):
    """Adaptive routing phase, standalone: route + stopping rule + traffic.

    Runs EXACTLY the routing stage of :func:`search_stacked` (same filter /
    liveness / tenant pushdown, same ``adaptive_prefix`` rule) and returns

      (gids [Q, P] i32, n_active [Q] i32, wins [G] i32, touches [G] i32)

    where ``wins[g]`` counts the queries whose routing WINNER (closest
    grain) is g and ``touches[g]`` counts active probes landing on g — the
    probe-traffic stats the hub set and ``grain_health`` consume.  The
    store's two-phase adaptive dispatch calls this first (one cheap [Q, G]
    routing pass), buckets queries by ``n_active`` on the host, and feeds
    the sliced plan back through ``search_stacked(probe_plan=...)`` so easy
    queries genuinely scan fewer grains (smaller static probe width), not
    just masked ones.  ``probe_margin=inf`` returns the static plan
    (all P active) — the identity bucket.

    grain_mask ([G] or [Q, G] bool): precomputed routing pushdown that
    REPLACES the in-jit filter/liveness/tenant pushdown.  The tiered
    residency path routes on a panel-free stub plane (zero-cap grains —
    the panels live on disk), so it computes the identical pushdown
    host-side from the memmapped panels and hands it in whole; passing it
    alongside tag_mask/ts_range/tenant_live is a contract violation (the
    caller owns the pushdown then).
    """
    index = stacked.index
    if grain_mask is not None:
        gmask = grain_mask
    else:
        extra, grain_ok = _mixed_recall_mask(index.grains, tag_mask,
                                             ts_range, live=stacked.live)
        gmask = _tenant_grain_mask(index.grains, extra, grain_ok,
                                   tenant_live, tenant_ix)
    gids, gd2 = routing.route(index.routing, q, nprobe, grain_mask=gmask)
    if math.isinf(probe_margin):
        n_active = jnp.full((q.shape[0],), gids.shape[1], jnp.int32)
    else:
        gids, n_active = routing.adaptive_prefix(
            gids, gd2, margin=probe_margin, min_probes=min_probes,
            hub_mask=hub_mask)
    g_n = index.routing.n_grains
    active = (jnp.arange(gids.shape[1], dtype=jnp.int32)[None, :]
              < n_active[:, None]).astype(jnp.int32)
    wins = jnp.zeros((g_n,), jnp.int32).at[gids[:, 0]].add(1)
    touches = jnp.zeros((g_n,), jnp.int32).at[gids].add(active)
    return gids, n_active, wins, touches


# ---------------------------------------------------------------------------
# Distributed fused search (grain-sharded across a mesh)
# ---------------------------------------------------------------------------


def _spec_tree(tree, spec):
    """Pytree of ``spec`` matching ``tree`` (explicit, version-portable
    alternative to relying on shard_map's prefix-spec matching)."""
    return jax.tree_util.tree_map(lambda _: spec, tree)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "grain_axis", "batch_axis", "nprobe", "pool",
                     "topk", "mode", "envelope_frac", "qeff", "scan_impl",
                     "budgets", "translate", "probe_margin", "min_probes"))
def search_stacked_sharded(plane: ShardedStackedSegments, q: jax.Array, *,
                           mesh, grain_axis: str = "model",
                           batch_axis: Optional[str] = None, nprobe: int,
                           pool: int, topk: int, mode: str = "B",
                           envelope_frac: float = 0.25, qeff: int = 8191,
                           scan_impl: Optional[str] = None,
                           budgets: Optional[tuple] = None,
                           translate: bool = True,
                           tag_mask: Optional[jax.Array] = None,
                           ts_range: Optional[tuple] = None,
                           tenant_live: Optional[jax.Array] = None,
                           tenant_ix: Optional[jax.Array] = None,
                           probe_margin: Optional[float] = None,
                           min_probes: int = 1,
                           hub_mask: Optional[jax.Array] = None
                           ) -> SearchResult:
    """Grain-sharded fused search: shard-local route/scan/pool/re-rank plus
    ONE top-k merge collective.

    The plane's grain panels, routing centroids, permuted raw tier and id
    table are all split along ``grain_axis`` (see ``store.shard_segments``
    for the shard-aligned layout).  Each shard independently runs the whole
    paper pipeline on its grain slice — top-P routing over its local
    centroids, envelope filter, Block-SoA scan, candidate pool, and (warm
    Mode B) the exact re-rank against its *own* raw slice — then translates
    to global ids locally and contributes its top-k to a single
    ``jax.lax.all_gather`` along ``grain_axis``; a replicated top-k over the
    gathered [Q, n_shards*k] pool is the entire merge epilogue.

    Knob semantics are per-shard: ``nprobe`` grains are probed and ``pool``
    candidates pooled (Mode B: re-ranked) on *each* shard, clamped to the
    local plane, so recall can only improve over the single-device plane
    with the same knobs, and per-shard scan work shrinks as shards are
    added.  Each shard contributes min(topk, pool) entries to the merge —
    ``pool`` caps the per-shard contribution in both modes, which is what
    lets the cold-tier caller request the full union of per-shard pools
    (topk = n_shards*pool) without inflating every shard's top-k and the
    all-gather payload by another factor of n_shards.  With exhaustive
    knobs the result is bit-for-bit identical to :func:`search_stacked`
    (the shard-count invariance tests).

    ``scan_impl`` picks the ScanPlane backend for every shard's candidate
    stage (the fused select kernel then runs per shard on its local panel
    slice, emitting only that shard's [Q, pool] candidate pool).
    ``batch_axis`` optionally shards queries over a second mesh axis
    (throughput scaling); results come back sharded the same way.
    ``translate=False`` returns *permuted global rows* (shard-local row +
    shard offset) for the host-side cold-tier re-rank.
    ``plane.live`` (the mutation-epoch tombstone/TTL bitmap, chunked along
    the grain axis like every panel) is applied in-situ inside each shard's
    scan, so a shard's Mode B re-rank can never resurrect a dead row of its
    own raw slice.
    ``tenant_live`` [T, SG, cap] + ``tenant_ix`` [Q] (multi-tenant
    coalesced serving): per-query visibility, sharded along the *grain*
    axis (dim 1 — the tenant axis replicates, see
    ``sharding.shard_plane_field(dim=1)``) so each shard holds exactly its
    grain slice of every tenant's bitmap; ``tenant_ix`` rides with the
    queries (replicated, or batch-sharded alongside them).

    Adaptive routing (``probe_margin``/``min_probes``/``hub_mask``) runs
    *in-jit per shard*: each shard applies the distance-gap stopping rule
    to its own local routing table, so per-shard probe budgets shrink
    independently (a query may be easy on one shard and hard on another).
    ``hub_mask`` is the global [G] hub bitmap, sharded along
    ``grain_axis`` like the centroids, so hub pinning stays shard-local.
    ``probe_margin=None`` (or inf) short-circuits to the static plane at
    trace time — bit-identical by construction.  No host bucketing here:
    the shard_map body is one fixed-shape program; killed probes are
    masked (and their panel DMAs deduped by the ragged kernel) in place.
    """
    adaptive = probe_margin is not None and not math.isinf(probe_margin)

    n_shards = mesh.shape[grain_axis]
    g_local = plane.index.grains.n_grains // n_shards
    cap = plane.index.grains.cap
    rows_local = plane.gid_of_row.shape[0] // n_shards
    probe = max(1, min(nprobe, g_local))
    slots = probe * cap
    # pool caps the per-shard contribution in BOTH modes (mode B also pools
    # before its re-rank); k_local is what each shard puts on the wire
    pool_eff = (min(max(pool, topk), slots) if mode == "B"
                else max(1, min(pool, slots)))
    k_local = min(topk, pool_eff)
    # budgets are per-shard knobs like nprobe/pool: the final stage must be
    # able to fill each shard's wire contribution, not the gathered width
    check_budgets(budgets, k_local)
    k_final = min(topk, n_shards * k_local)
    assert mode == "A" or plane.index.raw is not None, \
        "in-jit Mode B needs the warm tier; cold stores re-rank on host"

    def body(index, gid_local, live, qv, tm, tr, tliv, tix, hub):
        extra, grain_ok = _mixed_recall_mask(index.grains, tm, tr, live=live)
        gmask = _tenant_grain_mask(index.grains, extra, grain_ok, tliv, tix)
        gids, gd2 = routing.route(index.routing, qv, probe, grain_mask=gmask)
        n_active = None
        if adaptive:
            # per-shard stopping rule over the shard-local routing table;
            # hub is this shard's slice of the global hub bitmap
            gids, n_active = routing.adaptive_prefix(
                gids, gd2, margin=probe_margin, min_probes=min_probes,
                hub_mask=hub)
        # same ScanPlane backend per shard: the fused select kernel streams
        # this shard's probed panels and emits its [Q, pool_eff] pool only
        dists, rows = candidate_stage(
            index, qv, gids, envelope_frac=envelope_frac, qeff=qeff,
            width=max(pool_eff, k_local), scan_impl=scan_impl,
            budgets=budgets, extra_mask=extra, tenant_mask=tliv,
            tenant_ix=tix, n_active=n_active)

        def local_ids(rows_k, d_k):
            ok = jnp.logical_and(rows_k >= 0, d_k < BIG / 2)
            if translate:
                return jnp.where(ok, gid_local[jnp.maximum(rows_k, 0)],
                                 jnp.int32(-1))
            # permuted global rows, resolved on the host (cold tier)
            shard = jax.lax.axis_index(grain_axis)
            return jnp.where(ok, rows_k + shard * rows_local, -1)

        # shard-local epilogue (Mode B: the permuted raw tier is grain-
        # aligned, so every candidate this shard scanned lives in its own
        # raw slice) — shared with the single-device plane for parity
        local = _candidate_epilogue(dists, rows, qv, index.raw,
                                    pool=pool_eff, topk=k_local, mode=mode,
                                    translate=local_ids)
        # THE merge collective: one all-gather of the per-shard top-k pools
        g_ids, g_d = jax.lax.all_gather((local.ids, local.dists), grain_axis,
                                        axis=1, tiled=True)  # [Q, n*k_local]
        neg_f, pos_f = jax.lax.top_k(-g_d, k_final)
        return jnp.take_along_axis(g_ids, pos_f, axis=1), -neg_f

    q_spec = P(batch_axis) if batch_axis is not None else P(None)
    in_specs = (_spec_tree(plane.index, P(grain_axis)), P(grain_axis),
                _spec_tree(plane.live, P(grain_axis)), q_spec,
                _spec_tree(tag_mask, P()), _spec_tree(ts_range, P()),
                _spec_tree(tenant_live, P(None, grain_axis)),
                _spec_tree(tenant_ix, q_spec),
                _spec_tree(hub_mask, P(grain_axis)))
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=(q_spec, q_spec), check_vma=False)
    ids, d = fn(plane.index, plane.gid_of_row, plane.live, q, tag_mask,
                ts_range, tenant_live, tenant_ix, hub_mask)
    return SearchResult(ids=ids, dists=d)
