"""Trace spans of a store search, on the device trace's clock.

Every span is a ``jax.profiler.TraceAnnotation`` on the thread that makes
the call, so a profile captured with ``jax.profiler.trace`` holds them in
the same ``.xplane.pb`` as the device's ops, on one clock.  With no trace
active a span costs one check; nothing is recorded.

  hntl.search               one ``VectorStore.search`` call
                            (arguments ``queries``, ``call``)
    hntl.search.prepare     snapshot, argument checks, plane lookup, the
                            upload of the queries and filter scalars
    hntl.search.dispatch    one jitted search program's call, until it
                            returns (the device may still be running it)
    hntl.search.readback    ``jax.device_get`` of that program's results
    hntl.search.cold_rerank the cold tier's exact re-rank on the host
    hntl.search.finalize    memtable scan, final top-k, result upload
  hntl.plane.stack          a plane-cache miss: the segments (re)stacked
  hntl.plane.live           a liveness bitmap recomputed and uploaded
  hntl.gc                   one collection of Python's garbage collector
                            (argument ``generation``)

A call's stages tile it in order, one at a time: prepare, then a
dispatch/readback pair for each program it runs, then finalize.

On the device side the search programs carry ``jax.named_scope`` names in
their ops' metadata: ``hntl.route`` (routing), ``hntl.project`` (per-probe
projection) and ``hntl.rerank`` (the Mode A/B epilogue).  Each kernel is
named by its ``pallas_call``; the scan→select kernel's HLO instruction is
``fused_scan_select``.
"""
from __future__ import annotations

import gc

from jax.profiler import TraceAnnotation

SEARCH = "hntl.search"
PREPARE = "hntl.search.prepare"
DISPATCH = "hntl.search.dispatch"
READBACK = "hntl.search.readback"
COLD_RERANK = "hntl.search.cold_rerank"
FINALIZE = "hntl.search.finalize"
PLANE_STACK = "hntl.plane.stack"
PLANE_LIVE = "hntl.plane.live"
GC = "hntl.gc"

# ``jax.named_scope`` names of a search program's device work (metadata in
# the op's name stack; the kernels carry their own ``pallas_call`` names).
ROUTE = "hntl.route"        # centroid routing, the adaptive stopping rule
PROJECT = "hntl.project"    # per-probe tangent projection and quantization
RERANK = "hntl.rerank"      # Mode A top-k / Mode B exact re-rank epilogue

_tracing = TraceAnnotation.is_enabled


class SearchSpans:
    """The spans of one search call: ``hntl.search`` around it and, inside,
    one stage at a time.  ``stage(name)`` ends the open stage and begins
    ``name``; leaving the ``with`` block ends the last one.  A call that
    begins with no trace active records nothing, stages included."""

    __slots__ = ("_args", "_call", "_stage", "_name")

    def __init__(self, queries: int, call: int):
        self._args = (queries, call)
        self._call = None
        self._stage = None
        self._name = None

    def __enter__(self) -> "SearchSpans":
        if _tracing():
            queries, call = self._args
            self._call = TraceAnnotation(SEARCH, queries=queries, call=call)
            self._call.__enter__()
            self.stage(PREPARE)
        return self

    def stage(self, name: str) -> None:
        """End the open stage and begin ``name``; the stage that is open
        already goes on."""
        if self._call is None or name == self._name:
            return
        self._end_stage()
        self._stage = TraceAnnotation(name)
        self._stage.__enter__()
        self._name = name

    def _end_stage(self) -> None:
        if self._stage is not None:
            self._stage.__exit__(None, None, None)
            self._stage = self._name = None

    def __exit__(self, *exc) -> None:
        if self._call is not None:
            self._end_stage()
            self._call.__exit__(*exc)
            self._call = None


# A search's spans that are never entered, so ``stage`` records nothing:
# for the plane paths' callers other than ``VectorStore.search``.
UNTRACED = SearchSpans(queries=0, call=0)

# The span of the collection in progress.  The collector runs one
# collection at a time, on the thread whose allocation triggered it.
_gc_open: list = []


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        if _tracing():
            span = TraceAnnotation(GC, generation=info["generation"])
            span.__enter__()
            _gc_open.append(span)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


def install_gc_hook() -> None:
    """Give each collection of Python's collector an ``hntl.gc`` span.
    Installing it again does nothing."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def remove_gc_hook() -> None:
    while _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)
