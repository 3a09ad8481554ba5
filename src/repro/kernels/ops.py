"""Public jit'd wrappers around the Pallas HNTL scan kernels.

Backend policy:
  - "pallas"  : pl.pallas_call, compiled for TPU (interpret=False).
  - "interpret": same kernel body run by the Pallas interpreter — the CPU
    correctness-validation mode, only ever chosen by name.
  - "ref"     : pure-jnp oracle (XLA-fused); the semantics reference.
  - "auto"    : pallas on TPU, ref elsewhere.
Any other name is an error.

The sketch term (paper §2.2 s-dim residual sketch) is folded in by a second
kernel pass over the int8 sketch panels: Eq. 6 extends to
``||z_q - z_i||^2 + ||s_q - s_i||^2 + r_q + r_i`` where r now counts only the
energy outside span(W | S).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .hntl_scan import hntl_scan, hntl_scan_single

# Python-float copy of core.types.BIG (kept local so the kernels package
# stays importable without core).  Asserted equal in tests/test_kernels.py.
NEG_BIG = 3.0e38  # hntlint: ok H004


def default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


_BACKENDS = {"pallas": ("pallas", False), "interpret": ("pallas", True),
             "ref": ("ref", False)}


def _resolve(backend: str):
    if backend == "auto":
        backend = default_backend()
    try:
        return _BACKENDS[backend]
    except KeyError:
        raise ValueError(
            f"unknown scan backend {backend!r}; expected one of "
            f"{sorted(_BACKENDS) + ['auto']}") from None


def scan_batched(zq, rq, coords, res, valid, scale, res_scale,
                 sq=None, sketch=None, sketch_scale=None, extra_mask=None,
                 *, backend: str = "auto"):
    """Batched-query scan: P panels × Q queries (MXU path).

    zq [P, Q, k] i32, rq [P, Q] f32, coords [P, k, cap] i16, res [P, cap] i32,
    valid [P, cap] bool, scale/res_scale [P] f32.
    Optional sketch: sq [P, Q, s] i32, sketch [P, s, cap] i8, sketch_scale [P].
    Optional extra_mask [P, cap] bool (in-situ mixed-recall predicate).
    Returns [P, Q, cap] f32.
    """
    kind, interp = _resolve(backend)
    keep = valid if extra_mask is None else jnp.logical_and(valid, extra_mask)
    if kind == "ref":
        d = ref.hntl_scan_ref(zq, rq, coords, res, keep, scale, res_scale)
    else:
        d = hntl_scan(zq, rq, coords, res, keep, scale, res_scale,
                      interpret=interp)
    if sketch is not None:
        # The sketch pass computes ONLY ||s_q - s_i||^2 * sketch_scale^2:
        # residual inputs are identically zero, and the residual scale is a
        # self-describing neutral 1 — not some unrelated live scale riding
        # along (it multiplies zeros either way, but the call should say so).
        zero_r = jnp.zeros(res.shape, res.dtype)
        zero_rq = jnp.zeros(rq.shape, rq.dtype)
        unit_rs = jnp.ones_like(sketch_scale)
        allv = jnp.ones(valid.shape, bool)
        if kind == "ref":
            ds = ref.hntl_scan_ref(sq, zero_rq, sketch, zero_r, allv,
                                   sketch_scale, unit_rs)
        else:
            ds = hntl_scan(sq, zero_rq, sketch, zero_r, allv,
                           sketch_scale, unit_rs, interpret=interp)
        d = jnp.where(d < NEG_BIG / 2, d + ds, d)
    return d


def scan_single(zq, rq, coords, res, valid, scale, res_scale,
                sq=None, sketch=None, sketch_scale=None, extra_mask=None,
                *, backend: str = "auto"):
    """Single-query scan: P independent (panel, query) pairs (VPU path).

    zq [P, k] i32, rq [P] f32, coords [P, k, cap] i16, res/valid [P, cap],
    scale/res_scale [P].  Returns [P, cap] f32.
    """
    kind, interp = _resolve(backend)
    keep = valid if extra_mask is None else jnp.logical_and(valid, extra_mask)
    if kind == "ref":
        d = ref.hntl_scan_single_ref(zq, rq, coords, res, keep, scale,
                                     res_scale)
    else:
        d = hntl_scan_single(zq, rq, coords, res, keep, scale, res_scale,
                             interpret=interp)
    if sketch is not None:
        # sketch-only pass: zero residuals + neutral unit residual scale
        # (see scan_batched — the arg describes itself, nothing more)
        zero_r = jnp.zeros(res.shape, res.dtype)
        zero_rq = jnp.zeros(rq.shape, rq.dtype)
        unit_rs = jnp.ones_like(sketch_scale)
        allv = jnp.ones(valid.shape, bool)
        if kind == "ref":
            ds = ref.hntl_scan_single_ref(sq, zero_rq, sketch, zero_r, allv,
                                          sketch_scale, unit_rs)
        else:
            ds = hntl_scan_single(sq, zero_rq, sketch, zero_r, allv,
                                  sketch_scale, unit_rs, interpret=interp)
        d = jnp.where(d < NEG_BIG / 2, d + ds, d)
    return d


def make_planner_scan_fn(backend: str = "auto"):
    """Adapter matching ``core.scan.blocksoa_scan``'s (vmapped) signature so
    the query planner can run on the Pallas engine:
    zq [P,k] i32, rq [P] f32, coords [P,k,cap], ... -> [P, cap] f32.
    """
    def fn(zq, rq, coords, res, valid, scale, res_scale, sq=None, sketch=None,
           sketch_scale=None, extra_mask=None):
        return scan_single(zq, rq, coords, res, valid, scale, res_scale,
                           sq=sq, sketch=sketch, sketch_scale=sketch_scale,
                           extra_mask=extra_mask, backend=backend)
    return fn
