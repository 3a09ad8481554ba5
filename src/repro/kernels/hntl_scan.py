"""Pallas TPU kernel for the HNTL Block-SoA quantized scan (paper §3.3).

TPU adaptation of the paper's NEON/AVX engine (DESIGN.md §2): the scan is
lifted to query-batched matmul form so the MXU does the heavy lifting —

    D_int[Q, B] = ||zq||^2 1^T + 1 ||z_i||^2^T - 2 * Zq @ Z^T

exact in int32 because quantization is int32-safe
(core/index.int32_safe_qmax).  Mosaic has no int32 matmul, so the cross
term splits each operand into 7-bit limbs that bf16 holds exactly and runs
four bf16 MXU products with f32 accumulation (exact below 2^24), recombined
in int32 (``_exact_int_dot``).  Per-grain scales and residual terms are
fused into the epilogue, as is the validity / mixed-recall mask — the paper's
"in-situ predicate check inside the scan loop".

Layout: the coordinate panel arrives dimension-major `[k, cap]` (Block-SoA);
one (k, BLK_C) tile is resident in VMEM while query tiles stream — the VMEM
analogue of the paper's cache-line-aligned blocks.

Grid: (grains, query-tiles, cap-tiles).  Every block index is affine in the
grid — no gathers, no pointers anywhere in the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Python-float copy of core.types.BIG (Pallas kernels may not capture traced
# constants, and this package stays importable without core).  Must stay
# equal to types.BIG — asserted in tests/test_kernels.py.
NEG_BIG = 3.0e38  # hntlint: ok H004

BLK_Q = 128   # max query-tile rows (MXU dimension)
BLK_C = 128   # cap-tile columns    (lane dimension)


def _query_block(q: int) -> int:
    """Adaptive query-tile height: the next multiple of 8 (f32 sublane
    quantum) >= q, capped at BLK_Q.  The serving path's Q=1 then runs an
    8-row tile instead of burning a full 128-row MXU tile on padding."""
    return min(BLK_Q, -(-q // 8) * 8)


def _limbs(x):
    """int32 x -> bf16 (hi, lo) with x == hi * 128 + lo, lo in [0, 128).
    Exact in bf16 (8 significant bits) for |x| <= 2^15, i.e. any int16."""
    hi = jnp.right_shift(x, 7)
    lo = jnp.bitwise_and(x, 127)
    return (hi.astype(jnp.float32).astype(jnp.bfloat16),
            lo.astype(jnp.float32).astype(jnp.bfloat16))


def _exact_int_dot(a, b):
    """Exact int32 ``a @ b`` for int16-range a [M, k], b [k, N], k <= 256,
    on the MXU: every limb product is < 2^16 and every f32 partial sum of
    k of them stays below 2^24, so each of the four products is exact."""
    assert a.shape[1] <= 256, "limb partial sums must stay below 2^24"
    a_hi, a_lo = _limbs(a)
    b_hi, b_lo = _limbs(b)

    def dot(x, y):
        return jax.lax.dot_general(
            x, y, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)

    return (dot(a_hi, b_hi) * 16384
            + (dot(a_hi, b_lo) + dot(a_lo, b_hi)) * 128 + dot(a_lo, b_lo))


def _scan_kernel(zq_ref, rq_ref, coords_ref, res_ref, valid_ref,
                 scale_ref, res_scale_ref, out_ref):
    """One (grain g, query tile qi, cap tile ci) cell.

    zq_ref:     [BLK_Q, k] i32   — quantized queries in grain-g frame
    rq_ref:     [BLK_Q, 1] f32   — query residual energies (dequantized)
    coords_ref: [k, BLK_C] i16   — Block-SoA coordinate panel (dim-major)
    res_ref:    [1, BLK_C] i32   — quantized residual energies
    valid_ref:  [1, BLK_C] i32   — validity/mixed-recall mask (0/1)
    scale_ref:     [1, 1] f32    — Delta_g
    res_scale_ref: [1, 1] f32    — Delta_res,g
    out_ref:    [BLK_Q, BLK_C] f32
    """
    zq = zq_ref[...]                                   # i32 [BLK_Q, k]
    panel = coords_ref[...].astype(jnp.int32)          # [k, BLK_C]
    cross = _exact_int_dot(zq, panel)                  # [BLK_Q, BLK_C]
    zq2 = jnp.sum(zq * zq, axis=1, keepdims=True)      # [BLK_Q, 1]
    zi2 = jnp.sum(panel * panel, axis=0, keepdims=True)  # [1, BLK_C]
    d_int = zq2 + zi2 - 2 * cross                      # exact int32

    scale = scale_ref[0, 0]
    res_scale = res_scale_ref[0, 0]
    d = d_int.astype(jnp.float32) * (scale * scale)
    d = d + res_ref[...].astype(jnp.float32) * res_scale   # + r_i
    d = d + rq_ref[...]                                    # + r_q

    keep = valid_ref[...] != 0
    out_ref[...] = jnp.where(keep, d, jnp.float32(NEG_BIG))


@functools.partial(jax.jit, static_argnames=("interpret",))
def hntl_scan(zq, rq, coords, res, valid, scale, res_scale, *,
              interpret: bool = False):
    """Batched-query Block-SoA scan over P grain panels.

    Args (P grains, Q queries, k dims, cap slots; Q % BLK_Q == 0 handled by
    padding inside):
      zq     [P, Q, k] i32 — queries projected+quantized per grain frame
      rq     [P, Q] f32
      coords [P, k, cap] i16
      res    [P, cap] i32
      valid  [P, cap] bool
      scale, res_scale [P] f32

    Returns dists [P, Q, cap] f32 (+BIG on invalid slots).
    """
    p, q, k = zq.shape
    cap = coords.shape[2]
    blk_q = _query_block(q)
    q_pad = -q % blk_q
    c_pad = -cap % BLK_C
    if q_pad:
        zq = jnp.pad(zq, ((0, 0), (0, q_pad), (0, 0)))
        rq = jnp.pad(rq, ((0, 0), (0, q_pad)))
    if c_pad:
        coords = jnp.pad(coords, ((0, 0), (0, 0), (0, c_pad)))
        res = jnp.pad(res, ((0, 0), (0, 0), (0, c_pad)))
        valid = jnp.pad(valid, ((0, 0), (0, 0), (0, c_pad)))
    qp, capp = q + q_pad, cap + c_pad

    grid = (p, qp // blk_q, capp // BLK_C)  # affine — no pointers anywhere
    out = pl.pallas_call(
        _scan_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, blk_q, k), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((None, blk_q, 1), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((None, k, BLK_C), lambda g, i, j: (g, 0, j)),
            pl.BlockSpec((None, 1, BLK_C), lambda g, i, j: (g, 0, j)),
            pl.BlockSpec((None, 1, BLK_C), lambda g, i, j: (g, 0, j)),
            pl.BlockSpec((None, 1, 1), lambda g, i, j: (g, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda g, i, j: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (None, blk_q, BLK_C), lambda g, i, j: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((p, qp, capp), jnp.float32),
        interpret=interpret,
    )(
        zq,
        rq[..., None],
        coords,
        res[:, None, :],
        valid[:, None, :].astype(jnp.int32),
        scale[:, None, None],
        res_scale[:, None, None],
    )
    return out[:, :q, :cap]


# ---------------------------------------------------------------------------
# Single-query (VPU) variant — the serving path: one query per grain panel.
# ---------------------------------------------------------------------------


def _scan_single_kernel(zq_ref, rq_ref, coords_ref, res_ref, valid_ref,
                        scale_ref, res_scale_ref, out_ref):
    """One (panel p, cap tile ci) cell; Q == 1 so the MXU would idle —
    this is a pure VPU broadcast-subtract-square-reduce over the sublane
    (k) axis, the TPU analogue of the paper's NEON lane loop.

    zq_ref:     [k, 1] i32      coords_ref: [k, BLK_C] i16
    rq_ref:     [1, 1] f32      res_ref:    [1, BLK_C] i32
    valid_ref:  [1, BLK_C] i32  out_ref:    [1, BLK_C] f32
    """
    zq = zq_ref[...]                                    # [k, 1] i32
    panel = coords_ref[...].astype(jnp.int32)           # [k, BLK_C]
    diff = zq - panel                                   # broadcast over lanes
    d_int = jnp.sum(diff * diff, axis=0, keepdims=True)  # [1, BLK_C] exact i32
    scale = scale_ref[0, 0]
    d = d_int.astype(jnp.float32) * (scale * scale)
    d = d + res_ref[...].astype(jnp.float32) * res_scale_ref[0, 0]
    d = d + rq_ref[0, 0]
    keep = valid_ref[...] != 0
    out_ref[...] = jnp.where(keep, d, jnp.float32(NEG_BIG))


@functools.partial(jax.jit, static_argnames=("interpret",))
def hntl_scan_single(zq, rq, coords, res, valid, scale, res_scale, *,
                     interpret: bool = False):
    """Single-query Block-SoA scan over P independent grain panels.

    zq [P, k] i32, rq [P] f32, coords [P, k, cap] i16, res [P, cap] i32,
    valid [P, cap] bool, scale/res_scale [P] f32.  Returns [P, cap] f32.
    """
    p, k = zq.shape
    cap = coords.shape[2]
    c_pad = -cap % BLK_C
    if c_pad:
        coords = jnp.pad(coords, ((0, 0), (0, 0), (0, c_pad)))
        res = jnp.pad(res, ((0, 0), (0, c_pad)))
        valid = jnp.pad(valid, ((0, 0), (0, c_pad)))
    capp = cap + c_pad

    grid = (p, capp // BLK_C)
    out = pl.pallas_call(
        _scan_single_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, k, 1), lambda g, j: (g, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda g, j: (g, 0, 0)),
            pl.BlockSpec((None, k, BLK_C), lambda g, j: (g, 0, j)),
            pl.BlockSpec((None, 1, BLK_C), lambda g, j: (g, 0, j)),
            pl.BlockSpec((None, 1, BLK_C), lambda g, j: (g, 0, j)),
            pl.BlockSpec((None, 1, 1), lambda g, j: (g, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda g, j: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, BLK_C), lambda g, j: (g, 0, j)),
        out_shape=jax.ShapeDtypeStruct((p, 1, capp), jnp.float32),
        interpret=interpret,
    )(
        zq[:, :, None],
        rq[:, None, None],
        coords,
        res[:, None, :],
        valid[:, None, :].astype(jnp.int32),
        scale[:, None, None],
        res_scale[:, None, None],
    )
    return out[:, 0, :cap]
