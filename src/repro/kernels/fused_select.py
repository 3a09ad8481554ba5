"""Fused scan→select Pallas kernel: gather-free candidate generation.

The production planes used to (1) gather a per-query copy of every probed
panel (``coords[gids]`` — a [Q, P, k, cap] materialization), (2) write the
full [Q, P*cap] distance matrix to HBM, and (3) run one monolithic top-k.
This kernel is the paper's streaming engine instead (§3.3 applied to the
scan/select boundary):

- the probed grain ids arrive as a **scalar-prefetch** argument, so every
  block ``index_map`` computes its HBM offset from ``gids[q, p]`` and the
  pipeline streams only the probed ``[k, T]`` panels straight out of the
  stacked index — the [Q, P, k, cap] gather copy never exists;
- a per-query running candidate set (dists + rows + arrival order) lives
  in VMEM scratch and is carried across the sequential (probe, cap-tile)
  grid axes: each tile's candidates that beat the set's worst member evict
  it, one at a time (two-stage select), the set is sorted once when the
  query's last tile is done, and only the final [Q, width] pool is ever
  written to HBM — candidate state is O(Q·width) instead of
  O(Q·nprobe·cap).  The select uses only lane reductions and selects, which
  Mosaic lowers (it has no ``top_k`` or gather); ties keep ``lax.top_k``'s
  order, earliest (probe, slot) first;
- the epilogue folds everything the scan semantics need *in situ*: per-grain
  scales, the residual term, the §2.2 sketch term (previously a second full
  kernel pass in ``ops.scan_batched``), the envelope kill, and the combined
  validity/liveness/tag/ts mask.

Grid: (Q, P, capp/T), where capp is the capacity padded to whole 128-lane
columns and T = ``select_tile(capp, k, s)`` is the widest run of columns
whose double-buffered blocks fit a fixed VMEM budget: the whole grain at
the paper's shapes, so one grid step scores one probed grain.  A step's
fixed cost (index maps, DMA issue and wait, the merge's loop test) is paid
once per grain, not once per 128 slots.  The body computes the step's
distances 128 lanes at a time into a [T/128, 128] scratch (bounded live
vregs) and merges them into the carry in one pass.  The leading query axis
is embarrassingly parallel (each query owns its scratch carry — a megacore
split on q is safe), the trailing two axes are sequential reductions into
the carry.

Multi-tenant serving rides the same machinery with a SECOND scalar-prefetch
stream: the mask argument generalizes to a flattened [T*G, cap] per-tenant
visibility table and ``mgids[q, p] = tenant_ix[q] * G + gids[q, p]`` drives
its block index map, so every (query, probe) cell streams exactly its own
tenant's [1, T] mask tile.  No [Q, P, cap] per-query mask is ever
materialized — tenant state in HBM is O(T·G·cap), shared across queries —
and the no-tenant path simply passes ``mgids = gids`` with the usual
[G, cap] mask (same kernel, no extra cost).

Adaptive routing adds a THIRD scalar-prefetch stream: ``n_active`` [Q] i32
per-query active-probe counts (the ragged-probe vector).  The grid stays
static at the padded (Q, P, tiles) shape; probes ``p >= n_active[q]`` are
*killed* two ways at once:

- their block index maps clamp to ``min(p, n_active[q] - 1)`` — the
  pipeline sees the SAME block indices as the previous grid step, and the
  Pallas TPU pipeline skips the copy for an unchanged block, so a killed
  probe costs no HBM traffic (the DMA-dedupe property);
- the kernel body wraps distance work + carry merge in
  ``pl.when(p < n_active[q])``, so a killed probe's (re-resident) tile
  never touches the carry — in-situ masking, bit-identical to not having
  probed at all.

``n_active=None`` (or all-P) reduces to the static kernel by construction.

Tiered residency (``core.residency``) needs NOTHING from this kernel: the
residency manager materializes each staged cold chunk as an ordinary
mini stacked plane (a pure slice of the on-disk Block-SoA panels plus one
dummy grain), compacts the probe plan to local slots, and calls the same
scan→select entry points with ``probe_plan=``.  The kernel is
residency-oblivious by design — hot-tier and cold-chunk passes lower to
the identical kernel, which is what makes the paged search bit-identical
to the all-warm plane.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Python-float copy of core.types.BIG (Pallas kernels may not capture traced
# constants, and this package stays importable without core).  Must stay
# equal to types.BIG — asserted in tests/test_kernels.py.
NEG_BIG = 3.0e38  # hntlint: ok H004

BLK_C = 128   # lane column: caps pad to whole columns, distances are
              # computed one column at a time
# VMEM for one grid step's double-buffered grain blocks and its distance
# scratch: half of v5e's 16 MiB default scoped VMEM (select_tile).
TILE_VMEM_BYTES = 8 << 20
# Entries per flat scalar-prefetch stream (gids, mask gids: [Q*P] i32 each)
# in one kernel call: two 256 KiB streams use half of a v5e core's 1 MiB
# SMEM.  Larger batches are split along the query axis.
PREFETCH_ENTRIES = 1 << 16
_I32_MAX = 2 ** 31 - 1


def select_tile(capp: int, k: int, s: int) -> int:
    """Lane width T of one grid step over a padded capacity ``capp`` (a
    multiple of BLK_C) with ``k`` coordinate and ``s`` sketch rows (0 where
    the term is absent): the largest multiple of BLK_C that divides
    ``capp`` and whose blocks fit TILE_VMEM_BYTES, the whole grain when it
    fits.  Per lane, double-buffered: coords [k, T] i16 (16 sublanes a
    tile), sketch [s, T] i8 (32), res/mask/rows [1, T] i32 (8 each); plus
    the two [T/128, 128] scratch rows."""
    per_lane = 2 * (2 * _round_up(k, 16) + _round_up(s, 32) + 3 * 8 * 4) + 8
    cols = capp // BLK_C
    fits = [t for t in range(1, cols + 1)
            if cols % t == 0 and t * BLK_C * per_lane <= TILE_VMEM_BYTES]
    return BLK_C * max(fits, default=1)


def _sq_dist_int(z, panel):
    """Exact int32 ``sum_k (z_k - panel_k)^2`` per lane: z [k, 1] i32
    broadcast over the lanes of panel [k, BLK_C] -> [1, BLK_C].  A VPU
    reduction over the sublane (k) axis: Mosaic has no int32 matmul."""
    diff = z - panel.astype(jnp.int32)
    return jnp.sum(diff * diff, axis=0, keepdims=True)


def _merge_tile(tile_d, tile_r, arrival0, best_d, best_r, best_a,
                width: int):
    """Two-stage select, stage 2: fold one step's candidates, [T/128, 128]
    with slot ``row * 128 + lane``, into the running best-``width`` set held
    in lanes < width of the carry.

    While the tile's best candidate (smallest distance, lowest slot on a
    tie) beats the set's worst member (largest distance, latest arrival on
    a tie), it replaces that member.  A tile candidate that only ties the
    worst member loses: the member arrived earlier.  So the set is always
    the first ``width`` candidates seen under the order (distance, arrival),
    which is ``lax.top_k``'s order on the concatenated stream.
    """
    lane_t = (jax.lax.broadcasted_iota(jnp.int32, tile_d.shape, 0) * BLK_C
              + jax.lax.broadcasted_iota(jnp.int32, tile_d.shape, 1))
    lane_w = jax.lax.broadcasted_iota(jnp.int32, best_d.shape, 1)
    in_set = lane_w < width

    def worst(cd):
        return jnp.max(jnp.where(in_set, cd, -jnp.inf))

    # The loop state carries the tile's best distance m and the set's worst
    # v, so the loop test itself reduces nothing.
    def beats_worst(state):
        *_, m, v = state
        return m < v

    def replace_worst(state):
        td, cd, cr, ca, m, v = state
        i = jnp.min(jnp.where(td == m, lane_t, tile_d.size))
        r = jnp.sum(jnp.where(lane_t == i, tile_r, 0))
        at_v = jnp.logical_and(in_set, cd == v)
        a = jnp.max(jnp.where(at_v, ca, -1))
        e = jnp.max(jnp.where(jnp.logical_and(at_v, ca == a), lane_w, -1))
        hit = lane_w == e
        td = jnp.where(lane_t == i, jnp.inf, td)
        cd = jnp.where(hit, m, cd)
        return (td, cd, jnp.where(hit, r, cr),
                jnp.where(hit, arrival0 + i, ca), jnp.min(td), worst(cd))

    cd = best_d[...]
    _, cd, cr, ca, _, _ = jax.lax.while_loop(
        beats_worst, replace_worst,
        (tile_d, cd, best_r[...], best_a[...], jnp.min(tile_d), worst(cd)))
    best_d[...] = cd
    best_r[...] = cr
    best_a[...] = ca


def _sorted_set(best_d, best_r, best_a, width: int):
    """The carried set in ascending (distance, arrival) order: ``width``
    rounds of min-extraction.  Lanes >= width come back as (BIG, -1)."""
    lane_w = jax.lax.broadcasted_iota(jnp.int32, best_d.shape, 1)
    cr, ca = best_r[...], best_a[...]

    def take_min(i, state):
        cd, out_d, out_r = state
        cd = jnp.where(lane_w < width, cd, jnp.inf)
        m = jnp.min(cd)
        at_m = cd == m
        a = jnp.min(jnp.where(at_m, ca, _I32_MAX))
        e = jnp.min(jnp.where(jnp.logical_and(at_m, ca == a), lane_w,
                              _I32_MAX))
        r = jnp.sum(jnp.where(lane_w == e, cr, 0))
        out = lane_w == i
        return (jnp.where(lane_w == e, jnp.inf, cd),
                jnp.where(out, m, out_d), jnp.where(out, r, out_r))

    init = (best_d[...], jnp.full(best_d.shape, NEG_BIG, jnp.float32),
            jnp.full(best_r.shape, -1, jnp.int32))
    _, out_d, out_r = jax.lax.fori_loop(0, width, take_min, init)
    return out_d, out_r


def _make_select_kernel(has_coords: bool, has_sketch: bool, width: int):
    """Kernel body for one (query q, probe p, cap tile j) cell.

    ``has_coords=False`` leaves the tangent-coordinate term out statically
    (the cascade's stage 1 prices slots without it).  The §2.2
    residual-sketch term, when present, is folded into the SAME pass (the
    gathered plane pays a second full kernel launch for it) — everything
    else (carry lifecycle, in-situ predicate, emit) is single-sourced here.
    """

    def kernel(gids_ref, mgids_ref, na_ref, *refs):
        refs = list(refs)

        def take(present=True):          # in fused_scan_select's arg order
            return refs.pop(0) if present else None

        zq_ref = take(has_coords)
        rq_ref, keep_ref = take(), take()
        sq_ref = take(has_sketch)
        coords_ref = take(has_coords)
        res_ref, mask_ref, rows_ref = take(), take(), take()
        scale_ref = take(has_coords)
        res_scale_ref = take()
        sketch_ref, sk_scale_ref = take(has_sketch), take(has_sketch)
        out_d_ref, out_r_ref, best_d, best_r, best_a, tile_d, tile_r = refs
        n_cols = tile_d.shape[0]                 # 128-lane columns a step
        q_i, p_i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        n_tiles = pl.num_programs(2)

        @pl.when(jnp.logical_and(p_i == 0, j == 0))
        def _init():                                     # fresh query: reset
            best_d[...] = jnp.full(best_d.shape, NEG_BIG, best_d.dtype)
            best_r[...] = jnp.full(best_r.shape, -1, best_r.dtype)
            best_a[...] = jnp.full(best_a.shape, -1, best_a.dtype)

        # Ragged probes: killed cells (p >= n_active[q]) skip all distance
        # work and never touch the carry.  Their index maps clamp to the
        # last active probe's blocks, so the resident tiles this branch
        # skips cost no HBM traffic either.
        @pl.when(p_i < na_ref[q_i])
        def _scan():
            res_scale, rq = res_scale_ref[0, 0], rq_ref[0, 0]
            keep_q = keep_ref[0, 0] != 0
            if has_coords:
                zq, scale = zq_ref[...], scale_ref[0, 0]
            if has_sketch:
                sq, sk_scale = sq_ref[...], sk_scale_ref[0, 0]
            for c in range(n_cols):                  # one lane column each
                col = slice(c * BLK_C, (c + 1) * BLK_C)
                # Eq. 6, float op order of core.scan.blocksoa_scan (bit
                # parity)
                res_term = res_ref[:, col].astype(jnp.float32) * res_scale
                if has_coords:
                    d = _sq_dist_int(zq, coords_ref[:, col]).astype(
                        jnp.float32) * (scale * scale)
                    d = d + res_term + rq
                else:
                    d = res_term + rq
                if has_sketch:
                    d = d + _sq_dist_int(sq, sketch_ref[:, col]).astype(
                        jnp.float32) * (sk_scale * sk_scale)
                # in-situ predicate: validity ∧ liveness/tag/ts ∧ envelope
                keep = jnp.logical_and(mask_ref[:, col] != 0, keep_q)
                tile_d[c:c + 1, :] = jnp.where(keep, d,
                                               jnp.float32(NEG_BIG))
                tile_r[c:c + 1, :] = rows_ref[:, col]
            _merge_tile(tile_d[...], tile_r[...],
                        (p_i * n_tiles + j) * (n_cols * BLK_C),
                        best_d, best_r, best_a, width)

        last = jnp.logical_and(p_i == pl.num_programs(1) - 1,
                               j == n_tiles - 1)

        @pl.when(last)
        def _emit():                                     # the ONLY HBM write
            out_d, out_r = _sorted_set(best_d, best_r, best_a, width)
            out_d_ref[...] = out_d
            out_r_ref[...] = jnp.where(out_d < NEG_BIG / 2, out_r, -1)

    return kernel


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def fused_scan_select(gids, zq, rq, keep, coords, res, mask, rows, scale,
                      res_scale, sq=None, sketch=None, sketch_scale=None, *,
                      width: int, interpret: bool = False,
                      tenant_mask=None, tenant_ix=None, n_active=None):
    """Streaming scan→select over the probed grains of a stacked index.

    Args (Q queries, P probed grains/query, G total grains, cap slots/grain):
      gids   [Q, P] i32   — probed grain ids (scalar-prefetch: drives DMA)
      zq     [Q, P, k] i32 — query coords quantized per probed grain's frame
      rq     [Q, P] f32    — dequantized query residual energies
      keep   [Q, P] bool   — envelope-filter verdict (False kills the grain)
      coords [G, k, cap] i16 — the FULL stacked Block-SoA panel tier (only
                               probed [k, T] tiles are ever streamed)
      res    [G, cap] i32, mask [G, cap] bool (validity ∧ extra predicates),
      rows   [G, cap] i32 (payload row ids), scale/res_scale [G] f32.
      ``zq=None, coords=None``: leave the coordinate term out (the
      cascade's stage 1); ``scale`` is then unused.
      Optional sketch: sq [Q, P, s] i32, sketch [G, s, cap] i8,
      sketch_scale [G] f32 — folded into the same pass.
      Optional tenancy: tenant_mask [T, G, cap] bool + tenant_ix [Q] i32 —
      per-query visibility (coalesced multi-tenant serving).  Folded into
      the streamed mask via the second scalar-prefetch stream (see module
      docstring); the kernel body is tenant-oblivious.
      Optional adaptive routing: n_active [Q] i32 (1 <= n_active <= P) —
      per-query active-probe counts (the ragged-probe vector, third
      scalar-prefetch stream).  Probes p >= n_active[q] are killed in-situ
      with their block DMAs deduped away; None = all P probes active
      (bit-identical to the static formulation by construction).

    Returns (dists [Q, width] f32 ascending, rows [Q, width] i32); slots
    beyond the live candidates carry (BIG, -1).  The kernel is compiled
    for the TPU; ``interpret=True`` runs the same body in the Pallas
    interpreter (CPU validation) and is only ever chosen by the caller.
    """
    q_n, p_n = gids.shape
    g_n, cap = res.shape
    if tenant_mask is not None:
        # flatten tenants into the mask's leading axis; the second prefetch
        # stream addresses tenant t's grain g at row t*G + g
        mask = jnp.logical_and(tenant_mask, mask[None]) \
            .reshape(tenant_mask.shape[0] * g_n, cap)
    c_pad = -cap % BLK_C
    if c_pad:
        res = jnp.pad(res, ((0, 0), (0, c_pad)))
        mask = jnp.pad(mask, ((0, 0), (0, c_pad)))
        rows = jnp.pad(rows, ((0, 0), (0, c_pad)), constant_values=-1)
        if coords is not None:
            coords = jnp.pad(coords, ((0, 0), (0, 0), (0, c_pad)))
        if sketch is not None:
            sketch = jnp.pad(sketch, ((0, 0), (0, 0), (0, c_pad)))
    shared = dict(coords=coords, res=res, mask=mask, rows=rows, scale=scale,
                  res_scale=res_scale, sketch=sketch,
                  sketch_scale=sketch_scale)
    per_query = dict(gids=gids.astype(jnp.int32), zq=zq, rq=rq, keep=keep,
                     sq=sq, tenant_ix=tenant_ix,
                     n_active=(jnp.full((q_n,), p_n, jnp.int32)
                               if n_active is None
                               else n_active.astype(jnp.int32)))
    # Split the query axis so each call's prefetch streams fit in SMEM.
    q_chunk = max(1, PREFETCH_ENTRIES // p_n)
    outs = []
    for lo in range(0, q_n, q_chunk):
        part = {name: None if a is None else a[lo:lo + q_chunk]
                for name, a in per_query.items()}
        outs.append(_select_call(**part, **shared, g_n=g_n, width=width,
                                 interpret=interpret))
    if len(outs) == 1:
        return outs[0]
    return (jnp.concatenate([o[0] for o in outs]),
            jnp.concatenate([o[1] for o in outs]))


def _select_call(*, gids, zq, rq, keep, sq, tenant_ix, n_active, coords,
                 res, mask, rows, scale, res_scale, sketch, sketch_scale,
                 g_n: int, width: int, interpret: bool):
    """One pallas_call over a query chunk; the shared panels arrive padded
    to whole lane columns (and the mask tenant-flattened)."""
    has_coords = coords is not None
    q_n, p_n = gids.shape
    capp = res.shape[1]
    na = n_active
    # Scalar prefetch lives in SMEM, which pads a 2-D [Q, P] array to 128
    # lanes per row; flat [Q*P] streams keep large batches inside it.
    if tenant_ix is not None:
        mgids = (tenant_ix.astype(jnp.int32)[:, None] * g_n
                 + gids).reshape(q_n * p_n)
    else:
        mgids = gids.reshape(q_n * p_n)
    gids = gids.reshape(q_n * p_n)
    w_pad = _round_up(max(width, 1), 128)      # lane-aligned carry width
    tile = select_tile(capp, zq.shape[2] if has_coords else 0,
                       sq.shape[2] if sketch is not None else 0)

    grid = (q_n, p_n, capp // tile)

    # Block index maps: scalar-prefetched gids turn (q, p) into the probed
    # grain's HBM offset — affine streaming, no gather anywhere.  The mask
    # alone is addressed through the second prefetch stream (mg), which is
    # the per-(query, probe) row of the possibly-tenant-flattened table.
    # Every probe-indexed map clamps p to the query's last ACTIVE probe
    # (third prefetch stream): killed grid cells revisit the same block
    # indices as the previous step, and the pipeline skips the copy for an
    # unchanged block — a killed probe costs no DMA.
    def _pc(p, q, na):
        return jnp.minimum(p, na[q] - 1)

    def per_probe(q, p, j, g, mg, na):          # query-side [.., n, 1] rows
        return (q, _pc(p, q, na), 0, 0)

    def grain_tile(q, p, j, g, mg, na):         # probed grain's tile j
        return (g[q * p_n + _pc(p, q, na)], 0, j)

    def mask_tile(q, p, j, g, mg, na):
        return (mg[q * p_n + _pc(p, q, na)], 0, j)

    def grain_scalar(q, p, j, g, mg, na):
        return (g[q * p_n + _pc(p, q, na)], 0, 0)

    in_specs, args = [], []
    if has_coords:
        k = zq.shape[2]
        in_specs.append(pl.BlockSpec((None, None, k, 1), per_probe))
        args.append(zq[..., None])
    in_specs += [pl.BlockSpec((None, None, 1, 1), per_probe)] * 2
    args += [rq[:, :, None, None], keep[:, :, None, None].astype(jnp.int32)]
    if sketch is not None:
        s_dim = sq.shape[2]
        in_specs.append(pl.BlockSpec((None, None, s_dim, 1), per_probe))
        args.append(sq[..., None])
    if has_coords:
        in_specs.append(pl.BlockSpec((None, k, tile), grain_tile))
        args.append(coords)
    in_specs += [
        pl.BlockSpec((None, 1, tile), grain_tile),
        pl.BlockSpec((None, 1, tile), mask_tile),
        pl.BlockSpec((None, 1, tile), grain_tile),
    ]
    args += [res[:, None, :], mask[:, None, :].astype(jnp.int32),
             rows[:, None, :]]
    if has_coords:
        in_specs.append(pl.BlockSpec((None, 1, 1), grain_scalar))
        args.append(scale[:, None, None])
    in_specs.append(pl.BlockSpec((None, 1, 1), grain_scalar))
    args.append(res_scale[:, None, None])
    if sketch is not None:
        in_specs += [pl.BlockSpec((None, s_dim, tile), grain_tile),
                     pl.BlockSpec((None, 1, 1), grain_scalar)]
        args += [sketch, sketch_scale[:, None, None]]

    def per_query(q, p, j, g, mg, na):
        return (q, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((None, 1, w_pad), per_query)] * 2,
        scratch_shapes=[
            pltpu.VMEM((1, w_pad), jnp.float32),   # carried set: dists
            pltpu.VMEM((1, w_pad), jnp.int32),     # carried set: rows
            pltpu.VMEM((1, w_pad), jnp.int32),     # carried set: arrival
            pltpu.VMEM((tile // BLK_C, BLK_C), jnp.float32),  # step: dists
            pltpu.VMEM((tile // BLK_C, BLK_C), jnp.int32),    # step: rows
        ],
    )
    kernel = _make_select_kernel(has_coords, sketch is not None, width)
    out_d, out_r = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((q_n, 1, w_pad), jnp.float32),
            jax.ShapeDtypeStruct((q_n, 1, w_pad), jnp.int32),
        ],
        interpret=interpret,
        name="fused_scan_select",
    )(gids, mgids, na, *args)
    return out_d[:, 0, :width], out_r[:, 0, :width]
