"""The store's own spans, as the stage readers find them in ``View.host``.

``VectorStore.search`` opens ``hntl.search`` around each call on the
calling thread and, inside it, its stages one at a time (prepare, then a
dispatch/readback pair for each program it runs, then finalize); each
collection of Python's collector is an ``hntl.gc`` span.  The names are
the program's (``src/repro/core/spans.py``), copied here so that the
benchmark imports nothing of the program: where a program writes no such
span, the readers find no ``hntl.search`` and return None.
"""
from __future__ import annotations

from typing import Optional

SEARCH = "hntl.search"
PREPARE = "hntl.search.prepare"
DISPATCH = "hntl.search.dispatch"
READBACK = "hntl.search.readback"
FINALIZE = "hntl.search.finalize"
GC = "hntl.gc"


def ms_per_call(view, name: str, *,
                beyond_device: bool = False) -> Optional[float]:
    """Summed duration of the ``name`` spans over the store's search calls
    in the window, in ms; with ``beyond_device``, each span less the
    device-busy time inside it.  None where the trace holds no
    ``hntl.search``."""
    calls = sum(1 for e in view.host if e.name == SEARCH)
    if calls == 0:
        return None
    total = 0.0
    for e in view.host:
        if e.name == name:
            lo, hi = max(e.start, view.lo), min(e.end, view.hi)
            if hi > lo:
                total += hi - lo
                if beyond_device:
                    total -= view.busy_s(lo, hi)
    return total / calls * 1e3
