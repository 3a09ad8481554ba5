"""ScanPlane backend registry: pluggable candidate-generation engines.

The candidate stage of every search plane (legacy single-index, fused
stacked, grain-sharded) is one of two shapes:

- **gather** planes materialize a per-query copy of every probed panel
  (``coords[gids]``), scan it with a ``blocksoa_scan``-signature function,
  and hand the FULL [Q, nprobe*cap] distance matrix to the pooling stage.
- **select** planes stream probed panels straight from the stacked index
  and emit only the running top-``width`` pool — [Q, width] — so candidate
  HBM state is O(Q·pool) instead of O(Q·nprobe·cap).

Registered backends:

  name          kind     engine
  ------------  -------  --------------------------------------------------
  "ref"         gather   pure-jnp Block-SoA oracle (XLA-fused; CPU default)
  "pallas"      gather   Pallas scan kernels, compiled (TPU)
  "interpret"   gather   same kernels, interpreter mode (CPU validation)
  "fused"       select   scalar-prefetch fused scan→select Pallas kernel,
                         compiled for the TPU
  "fused_interpret"
                select   the same kernel, interpreter mode (CPU validation)
  "fused_ref"   select   jnp two-stage-select oracle of the fused kernel
  "cascade"     select   mixed-precision 3-stage cascade (sketch filter →
                         quantized re-price → exact re-rank), staged:
                         accepts budgets=(b1, b2); stage 1 on the fused
                         kernel, compiled for the TPU
  "cascade_interpret"
                select   the cascade with stage 1 on the interpreted kernel
  "cascade_ref" select   the cascade with stage 1 on the jnp oracle
  "auto"        —        "fused" on TPU, "ref" elsewhere

A compiled kernel never falls back to the interpreter or to a reference:
interpreter mode is chosen only by its name.

Every planner entry point and ``VectorStore.search`` accept the backend by
name (``scan_impl=...``); the name is a jit static, and the store keys its
plane cache on the *resolved* name, so aliases ("auto"/None vs what they
resolve to) share one cached device plane while each distinct backend gets
its own LRU slot.  ``register_scan_plane`` extends the table (e.g. an
external accelerator engine).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax

from . import cascade, scan
from ..kernels import ops as kernel_ops
from ..kernels.fused_select import fused_scan_select

GATHER = "gather"
SELECT = "select"


@dataclasses.dataclass(frozen=True)
class ScanPlane:
    """One candidate-generation backend.

    ``runner`` signatures by kind:
      gather: ``blocksoa_scan``-compatible (vmapped by the planner over the
        gathered [Q, P, ...] panels) -> dists [P, cap].
      select: ``fused_scan_select``-compatible (gids, zq, rq, keep, coords,
        res, mask, rows, scale, res_scale, [sq, sketch, sketch_scale], *,
        width) -> (dists [Q, width], rows [Q, width]).

    ``staged`` backends additionally accept ``budgets=(b1, b2)`` per-stage
    survivor budgets (the mixed-precision cascade); passing budgets to a
    non-staged backend is a validation error.

    ``adaptive`` select backends accept ``n_active=`` ([Q] i32 per-query
    active-probe counts, adaptive routing's ragged-probe vector) and kill
    probes p >= n_active[q] in-situ.  Gather backends need no flag — the
    planner folds n_active into the envelope verdict before the scan.
    Routing an adaptive plan to a select backend without the flag is a
    validation error (external registrations opt in explicitly).
    """

    name: str
    kind: str
    runner: Callable
    doc: str = ""
    staged: bool = False
    adaptive: bool = False


_REGISTRY: dict = {}


def register_scan_plane(name: str, kind: str, runner: Callable,
                        doc: str = "", staged: bool = False,
                        adaptive: bool = False) -> ScanPlane:
    assert kind in (GATHER, SELECT), kind
    plane = ScanPlane(name=name, kind=kind, runner=runner, doc=doc,
                      staged=staged, adaptive=adaptive)
    _REGISTRY[name] = plane
    return plane


def scan_plane_names() -> tuple:
    """Registered backend names (+ "auto"), for CLI choices and docs."""
    return tuple(_REGISTRY) + ("auto",)


def get_scan_plane(name: Optional[str]) -> ScanPlane:
    """Resolve a backend name (None == "auto") to its ScanPlane."""
    if name is None or name == "auto":
        name = "fused" if jax.default_backend() == "tpu" else "ref"
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown scan plane {name!r}; registered: "
            f"{sorted(scan_plane_names())}") from None


register_scan_plane(
    "ref", GATHER, scan.blocksoa_scan,
    "pure-jnp Block-SoA oracle (XLA-fused; the CPU default and the "
    "semantics reference every other backend is tested against)")
register_scan_plane(
    "pallas", GATHER, kernel_ops.make_planner_scan_fn("pallas"),
    "Pallas Block-SoA scan kernels compiled for TPU (gathered panels, "
    "full distance matrix)")
register_scan_plane(
    "interpret", GATHER, kernel_ops.make_planner_scan_fn("interpret"),
    "the Pallas scan kernels in interpreter mode — validates the exact "
    "TPU kernel body on CPU")
register_scan_plane(
    "fused", SELECT, fused_scan_select,
    "scalar-prefetch fused scan→select kernel: gather-free panel "
    "streaming + in-VMEM running top-k, compiled for the TPU",
    adaptive=True)
register_scan_plane(
    "fused_interpret", SELECT,
    functools.partial(fused_scan_select, interpret=True),
    "the fused scan→select kernel in interpreter mode — validates the "
    "exact TPU kernel body on CPU", adaptive=True)
register_scan_plane(
    "fused_ref", SELECT, scan.blocksoa_select_ref,
    "jnp two-stage-select oracle of the fused kernel (CPU oracle for the "
    "select contract)", adaptive=True)
register_scan_plane(
    "cascade", SELECT, cascade.make_cascade_runner("kernel"),
    "mixed-precision cascade: §2.2 sketch/residual filter (stage 1, the "
    "fused kernel without the coordinate term) → quantized tangent-coord "
    "re-price of the b1 survivors (stage 2) → exact raw re-rank (stage 3, "
    "the shared epilogue); accepts budgets=(b1, b2)", staged=True,
    adaptive=True)
register_scan_plane(
    "cascade_interpret", SELECT, cascade.make_cascade_runner("interpret"),
    "the cascade with stage 1 on the interpreted fused kernel (CPU "
    "validation of the staged contract)", staged=True, adaptive=True)
register_scan_plane(
    "cascade_ref", SELECT, cascade.make_cascade_runner("ref"),
    "the cascade with stage 1 on the jnp select oracle (fast CPU parity "
    "path for the staged contract)", staged=True, adaptive=True)
