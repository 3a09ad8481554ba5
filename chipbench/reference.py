"""The plain reference: exact nearest neighbours by brute force.

It imports nothing of the store and takes nothing the store made.  The
exact top-k runs on the device at full float32 precision (a TPU's default
float32 matmul rounds its inputs to bfloat16), a block of queries at a
time; the distances of returned ids are recomputed on the host in
float64.  ``Bf16Search`` is the control: the same brute force in the next
precision down, bfloat16, put where the store would be.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128


@functools.partial(jax.jit, static_argnames=("topk", "low"))
def _topk_block(x, x2, q, *, topk: int, low: bool):
    if low:
        cross = jnp.matmul(q.astype(jnp.bfloat16), x.T,
                           preferred_element_type=jnp.float32)
        qb = q.astype(jnp.bfloat16).astype(jnp.float32)
        q2 = jnp.sum(qb * qb, axis=-1, keepdims=True)
    else:
        cross = jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST)
        q2 = jnp.sum(q * q, axis=-1, keepdims=True)
    d2 = q2 - 2.0 * cross + x2[None, :]
    neg, ids = jax.lax.top_k(-d2, topk)
    return ids, -neg


@jax.jit
def _sq_norms(x, keep=None):
    """Each row's squared norm; +inf for a row that ``keep`` leaves out, so
    no query ranks it."""
    xf = x.astype(jnp.float32)
    x2 = jnp.sum(xf * xf, axis=-1)
    return x2 if keep is None else jnp.where(keep, x2, jnp.inf)


def keep_rows(ts: np.ndarray, ts_range=None):
    """The rows a search with ``ts_range`` (lo <= ts < hi) may return, as a
    boolean array, or None where every row may."""
    if ts_range is None:
        return None
    lo, hi = ts_range
    return (ts >= lo) & (ts < hi)


def exact_topk(x, q: np.ndarray, topk: int, *, low: bool = False,
               keep=None):
    """Top-k by squared L2 of host queries ``q`` [Q, d] against the device
    corpus ``x`` [N, d] (float32, or bfloat16 when ``low``), over the rows
    ``keep`` holds (all by default).  Returns host (ids [Q, topk] int64,
    dists [Q, topk] float32)."""
    x2 = _sq_norms(x, keep)
    n_q = q.shape[0]
    block = min(QUERY_BLOCK, n_q)
    pad = -n_q % block
    qp = np.concatenate([q, np.zeros((pad, q.shape[1]), q.dtype)])
    ids, ds = [], []
    for lo in range(0, qp.shape[0], block):
        i, d = _topk_block(x, x2, jnp.asarray(qp[lo:lo + block]),
                           topk=topk, low=low)
        ids.append(i)
        ds.append(d)
    ids = np.concatenate([np.asarray(i) for i in ids])[:n_q]
    ds = np.concatenate([np.asarray(d) for d in ds])[:n_q]
    return ids.astype(np.int64), ds.astype(np.float32)


def host_sq_dists(x: np.ndarray, q: np.ndarray, ids: np.ndarray,
                  block: int = 2048) -> np.ndarray:
    """Squared L2 in float64 between each query row ``q[r]`` and the corpus
    rows ``ids[r, :]`` it was answered with; NaN where an id is not a row
    of ``x``."""
    n = x.shape[0]
    out = np.full(ids.shape, np.nan)
    for lo in range(0, ids.shape[0], block):
        i = ids[lo:lo + block]
        ok = (i >= 0) & (i < n)
        rows = x[np.where(ok, i, 0)].astype(np.float64)
        d = np.sum((rows - q[lo:lo + block, None, :].astype(np.float64))
                   ** 2, axis=-1)
        out[lo:lo + block] = np.where(ok, d, np.nan)
    return out


def recall_hits(ids: np.ndarray, truth: np.ndarray) -> np.ndarray:
    """Per row, how many of the true top-k ids the answer holds (each true
    id counted once, whatever the answer repeats)."""
    return (truth[:, :, None] == ids[:, None, :]).any(-1).sum(-1)


class Bf16Search:
    """The control: exact search in bfloat16 in the store's place."""

    def __init__(self, x: np.ndarray, topk: int, keep=None):
        xf = jax.device_put(x)
        self.x = xf.astype(jnp.bfloat16)
        xf.delete()
        self.x2 = _sq_norms(self.x, keep)
        self.topk = topk

    def search(self, q: np.ndarray):
        ids, d = _topk_block(self.x, self.x2, jnp.asarray(q), topk=self.topk,
                             low=True)
        return np.asarray(ids, np.int64), np.asarray(d, np.float32)

    def close(self) -> None:
        self.x.delete()
        self.x2.delete()
