"""Exact brute-force oracle (ground truth for recall measurements)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .types import SearchResult


@functools.partial(jax.jit, static_argnames=("topk",))
def flat_search(x: jax.Array, q: jax.Array, topk: int = 10,
                valid: jax.Array | None = None) -> SearchResult:
    """Exact L2^2 top-k.  x [N, d], q [Q, d]; valid [N] bool keeps only
    those rows (filters, deletes).  The cross term runs at full f32
    precision: a TPU's default f32 matmul rounds its inputs to bf16, which
    would make the oracle itself approximate."""
    x2 = jnp.sum(x * x, axis=-1)
    q2 = jnp.sum(q * q, axis=-1, keepdims=True)
    cross = jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST)
    d2 = q2 - 2.0 * cross + x2[None, :]
    if valid is not None:
        d2 = jnp.where(valid[None, :], d2, jnp.inf)
    neg_d, ids = jax.lax.top_k(-d2, topk)
    return SearchResult(ids=ids.astype(jnp.int32), dists=-neg_d)


def recall_at_k(pred_ids, true_ids) -> float:
    """Mean fraction of true top-k found in predicted top-k."""
    import numpy as np

    pred = np.asarray(pred_ids)
    true = np.asarray(true_ids)
    hits = 0
    for p, t in zip(pred, true):
        hits += len(set(p.tolist()) & set(t.tolist()))
    return hits / true.size
