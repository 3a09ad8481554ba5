"""Mixed-precision cascade select stage (density-aware staged re-rank).

The fused select plane prices every probed slot with the FULL quantized
distance before any pruning.  The cascade restructures candidate generation
into three explicit stages with per-stage survivor budgets ``(b1, b2)``:

  stage 1 — §2.2 sketch/residual filter: every probed slot is priced at
    the cheap remainder of the scan distance (residual energy term + query
    residual + sketch term — everything EXCEPT the coordinate term, which
    is >= 0).  The pricing runs through the existing select machinery with
    the coordinate term left out, so the PR 4 kernel's scalar-prefetch
    streaming and in-VMEM running top-k carry the stage for free: only the
    top-``b1`` flat slots survive, and the [Q, P*cap] matrix never exists.
  stage 2 — quantized tangent-coordinate distance: the b1 survivors'
    coordinate columns are gathered (a [Q, b1, k] touch instead of the
    full [Q, P, k, cap] panel copy) and re-priced with the exact
    Block-SoA arithmetic — identical float op order to ``scan
    .blocksoa_scan`` — keeping the top-``b2``.
  stage 3 — exact raw re-rank: the shared ``planner._candidate_epilogue``
    (Mode B) re-ranks the b2 survivors against the raw tier, unchanged.

With ``budgets=None`` stage 1 keeps every probed slot (b1 = P*cap) and
stage 2 reduces to the full scan — the cascade is then bit-identical to
the "ref"/"fused" planes by construction, which is what the conformance
suite pins.  With budgets set, recall is held by stage 3 as long as the
final budget covers ``topk``; smaller budgets raise at validation time.

Mixed precision needs no special handling here: per-grain int4/int8 widths
only change how ``coords`` and ``scale`` were FIT (``GrainStore.qmaxg``);
every backend reads the same stored panels, so cascade parity is
width-independent.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..kernels.fused_select import fused_scan_select
from . import scan
from .types import BIG


def check_budgets(budgets, topk: int) -> None:
    """Host/trace-time validation of per-stage survivor budgets."""
    if budgets is None:
        return
    if len(budgets) != 2:
        raise ValueError(f"budgets must be (b1, b2), got {budgets!r}")
    b1, b2 = int(budgets[0]), int(budgets[1])
    if not b1 >= b2 >= 1:
        raise ValueError(
            f"stage budgets must satisfy b1 >= b2 >= 1, got {budgets!r}")
    if b2 < topk:
        raise ValueError(
            f"final-stage survivor budget {b2} < topk {topk}: the exact "
            "re-rank could never fill the result; raise b2 or lower topk")


def _stage1_filter(engine: str, gids, rq, keep, res, mask, scale, res_scale,
                   sq, sketch, sketch_scale, tenant_mask, tenant_ix,
                   b1: int, n_active=None):
    """Stage 1: cheap filter over every probed slot, coordinate term left
    out.

    The scan distance is  coord_term + res*res_scale + rq (+ sketch_term)
    with coord_term >= 0, so pricing a slot without it gives exactly the
    cheap remainder.  The kernel engines drop the term statically; the jnp
    oracle scans a zero coordinate panel (k=1, all zeros, query coords 0),
    which gives the same floats.  Every mask (validity/liveness/tag/ts/
    envelope/tenant) is applied by the underlying select engine.  Returns
    (d1 [Q, b1] f32 ascending, fs [Q, b1] i32 flat slots g*cap + c,
    -1 = pruned).
    """
    g_n, cap = res.shape
    q_n, p_n = gids.shape
    fsl = (jnp.arange(g_n, dtype=jnp.int32)[:, None] * cap
           + jnp.arange(cap, dtype=jnp.int32)[None, :])
    kw = {}
    if sketch is not None:
        kw = dict(sq=sq, sketch=sketch, sketch_scale=sketch_scale)
    if tenant_mask is not None:
        kw.update(tenant_mask=tenant_mask, tenant_ix=tenant_ix)
    if n_active is not None:
        # ride the ragged-probe stream too: the keep fold already kills the
        # probes semantically, n_active= additionally dedupes their DMAs
        kw["n_active"] = n_active
    if engine == "ref":
        zq1 = jnp.zeros((q_n, p_n, 1), jnp.int32)
        z1 = jnp.zeros((g_n, 1, cap), jnp.int16)
        return scan.blocksoa_select_ref(gids, zq1, rq, keep, z1, res, mask,
                                        fsl, scale, res_scale, width=b1, **kw)
    return fused_scan_select(gids, None, rq, keep, None, res, mask, fsl,
                             scale, res_scale, width=b1,
                             interpret=engine == "interpret", **kw)


def make_cascade_runner(stage1_engine: str):
    """Build a select-plane runner for the cascade backend.

    stage1_engine: "kernel" — stage 1 rides the fused scalar-prefetch
    Pallas kernel, compiled for the TPU; "interpret" — the same kernel in
    the Pallas interpreter (CPU validation); "ref" — stage 1 uses the jnp
    two-stage-select oracle (fast CPU parity path).
    """
    assert stage1_engine in ("kernel", "interpret", "ref"), stage1_engine

    def cascade_select(gids, zq, rq, keep, coords, res, mask, rows, scale,
                       res_scale, sq=None, sketch=None, sketch_scale=None, *,
                       width: int, budgets: Optional[tuple] = None,
                       tenant_mask=None, tenant_ix=None, n_active=None):
        g_n, k, cap = coords.shape
        q_n, p_n = gids.shape[:2]
        slots = p_n * cap
        if n_active is not None:
            # adaptive routing: killed probes fold into the keep verdict
            # BEFORE stage 1, so the whole cascade (cheap filter, re-price,
            # budgets) only ever prices active grains
            keep = jnp.logical_and(
                keep, jnp.arange(p_n, dtype=jnp.int32)[None, :]
                < n_active[:, None])
        if budgets is None:
            b1, b2 = slots, width            # lossless: prune nothing
        else:
            check_budgets(budgets, 1)
            b1 = max(1, min(int(budgets[0]), slots))
            b2 = max(1, min(int(budgets[1]), width, b1))

        d1, fs = _stage1_filter(stage1_engine, gids, rq, keep, res, mask,
                                scale, res_scale, sq, sketch, sketch_scale,
                                tenant_mask, tenant_ix, b1,
                                n_active=n_active)
        del d1                               # ranking only; re-priced below

        # ---- stage 2: full quantized distance on the b1 survivors -------
        fs_c = jnp.maximum(fs, 0)
        g_of = fs_c // cap                                    # [Q, b1]
        c_of = fs_c % cap
        eq = gids[:, None, :] == g_of[:, :, None]             # [Q, b1, P]
        ok = jnp.logical_and(fs >= 0, jnp.any(eq, axis=-1))
        p_of = jnp.argmax(eq, axis=-1)                        # probe index
        zq_s = jnp.take_along_axis(zq, p_of[..., None], axis=1)  # [Q,b1,k]
        rq_s = jnp.take_along_axis(rq, p_of, axis=1)
        c_s = coords[g_of, :, c_of].astype(jnp.int32)         # [Q, b1, k]
        d_int = jnp.sum((zq_s - c_s) ** 2, axis=-1)           # exact int32
        sc_s = scale[g_of]
        # float op order matches scan.blocksoa_scan exactly (bit parity)
        d = d_int.astype(jnp.float32) * (sc_s * sc_s)
        d = d + res[g_of, c_of].astype(jnp.float32) * res_scale[g_of] + rq_s
        if sketch is not None:
            sq_s = jnp.take_along_axis(sq, p_of[..., None], axis=1)
            sk_s = sketch[g_of, :, c_of].astype(jnp.int32)    # [Q, b1, s]
            s_int = jnp.sum((sq_s - sk_s) ** 2, axis=-1)
            ss_s = sketch_scale[g_of]
            d = d + s_int.astype(jnp.float32) * (ss_s * ss_s)
        d = jnp.where(ok, d, BIG)

        # ---- top-b2 survivors, padded to the [Q, width] select contract -
        take = min(width, d.shape[1])
        neg, pos = jax.lax.top_k(-d, take)
        out_d = -neg
        go = jnp.take_along_axis(g_of, pos, axis=1)
        co = jnp.take_along_axis(c_of, pos, axis=1)
        out_r = rows[go, co]                                  # payload rows
        if take < width:
            out_d = jnp.pad(out_d, ((0, 0), (0, width - take)),
                            constant_values=BIG)
            out_r = jnp.pad(out_r, ((0, 0), (0, width - take)),
                            constant_values=-1)
        if b2 < width:                       # stage-2 survivor budget
            out_d = jnp.where(jnp.arange(width) < b2, out_d, BIG)
        out_r = jnp.where(out_d < BIG / 2, out_r, -1)
        return out_d, out_r

    return cascade_select
