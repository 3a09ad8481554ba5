"""The work the scan→select stage needs, counted from shapes, and the
least time a chip could take for it.

The count is of the algorithm, not of the current kernel's traffic or
layout: every probed (query, grain) pair prices the rows the grain holds
(its real size, not the padded slot capacity) with ``k`` coordinate and
``s`` sketch terms (a difference, a square and an add folded to two
operations each); every distinct probed grain's panels are read once,
however many queries probe it.  A kernel that streams a grain once for
many queries reads the same work, so the share can never pass 100%.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"

# bytes per panel slot: coords int16 x k, sketch int8 x s, and three
# 32-bit words (quantized residual, mask, payload row id)
_WORDS_PER_SLOT = 3


def fused_select_work(*, queries: int, probes: int, probed_rows: int,
                      distinct_rows: int, k: int, s: int, pool: int):
    """(operations, bytes) of one scan→select call.  ``probed_rows`` sums
    the probed grain's rows over every (query, probe) pair;
    ``distinct_rows`` sums the rows of the distinct grains probed."""
    ops = probed_rows * 2 * (k + s)
    panel = distinct_rows * (2 * k + s + 4 * _WORDS_PER_SLOT)
    # per (query, probe): quantized coords and sketch (int32), residual
    # energy and envelope verdict (32-bit); out: pool x (dist, row)
    query_in = queries * probes * (k + s + 2) * 4
    out = queries * pool * 8
    return ops, panel + query_in + out


def peaks_for(device_kind: str, path=PEAKS) -> dict:
    """The published peaks of one chip of this kind.  An unknown kind is
    an error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table['devices'])}")
    return table["devices"][device_kind]


def least_time(ops: float, nbytes: float, peaks: dict):
    """(seconds, bound): the larger of operations over the bf16 peak and
    bytes over the memory bandwidth, and which of the two it is."""
    t_ops = ops / peaks["bf16_flops"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def routed_rows(cents: np.ndarray, sizes: np.ndarray, q: np.ndarray,
                probes: int):
    """(probed_rows, distinct_rows) of a batch's exact top-``probes``
    routing over the grain centroids (empty grains are never routed): the
    grains' rows summed over every (query, probe) pair, and over the
    distinct grains."""
    d2 = (np.sum(q.astype(np.float64) ** 2, axis=1, keepdims=True)
          - 2.0 * q.astype(np.float64) @ cents.astype(np.float64).T
          + np.sum(cents.astype(np.float64) ** 2, axis=1)[None, :])
    d2[:, sizes <= 0] = np.inf
    top = np.argpartition(d2, probes - 1, axis=1)[:, :probes]
    sizes = sizes.astype(np.int64)
    return int(sizes[top].sum()), int(sizes[np.unique(top)].sum())
