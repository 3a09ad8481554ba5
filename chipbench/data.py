"""Corpus and query generation on the device, from the run's seed.

A ``jax.random`` copy of the repo's anisotropic-manifold recipe (latent
u ~ N(0, I_m), a random linear embedding plus quadratic bending terms, then
isotropic ambient noise), made row block by row block on the device and
scaled to unit length, so squared L2 ranks the rows as cosine does.

A deployment holds one collection, as a benchmark's dataset is one: its
points are drawn from a fixed key, the same for every seed, so every run
builds the same index (grain sizes, slot capacity) and does the same work.
The seed draws the queries (fresh points of the same manifold: held out,
not corpus points), the per-row ``ts`` and the traffic's order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
COLLECTION = 0      # the key the collection's points are drawn from


def key_of(seed: int, stream: int) -> jax.Array:
    """A PRNG key for one named stream of a run.  Any whole seed works:
    it is taken modulo 2**64 and split into two 32-bit words."""
    s = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(s & 0xFFFFFFFF)
    key = jax.random.fold_in(key, s >> 32)
    return jax.random.fold_in(key, stream)


def host_rng(seed: int, stream: int) -> np.random.Generator:
    s = int(seed) % (1 << 64)
    return np.random.default_rng([s & 0xFFFFFFFF, s >> 32, stream])


def _block_rows(n: int, limit: int = 65536) -> int:
    """The largest divisor of n that is at most ``limit``."""
    for b in range(min(n, limit), 0, -1):
        if n % b == 0:
            return b
    return 1


@functools.partial(jax.jit, static_argnames=("n", "d", "intrinsic"))
def manifold(key, embed_key, *, n: int, d: int, intrinsic: int,
             curvature: float, noise: float) -> jax.Array:
    """[n, d] float32 unit rows on one manifold.  ``embed_key`` fixes the
    manifold; ``key`` draws the points."""
    ka, kb, kp = jax.random.split(embed_key, 3)
    nq = intrinsic // 2
    a = jax.random.normal(ka, (intrinsic, d)) / np.sqrt(intrinsic)
    b = jax.random.normal(kb, (nq, d)) / np.sqrt(nq)
    pairs = jax.random.randint(kp, (nq, 2), 0, intrinsic)
    rows = _block_rows(n)

    def block(i):
        ku, kn = jax.random.split(jax.random.fold_in(key, i))
        u = jax.random.normal(ku, (rows, intrinsic))
        quad = u[:, pairs[:, 0]] * u[:, pairs[:, 1]]
        x = (jnp.matmul(u, a, precision=_HI)
             + curvature * jnp.matmul(quad, b, precision=_HI)
             + noise * jax.random.normal(kn, (rows, d)))
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True))

    out = jax.lax.map(block, jnp.arange(n // rows))
    return out.reshape(n, d)


def make(seed: int, cfg: dict):
    """Corpus [N, d] on the device, queries [n_queries, d] and per-row
    ``ts`` on the host: everything a run needs, from its seed."""
    gen = cfg["data"]
    kw = dict(d=cfg["d"], intrinsic=gen["intrinsic"],
              curvature=gen["curvature"], noise=gen["noise"])
    embed = key_of(COLLECTION, 0)
    x = manifold(key_of(COLLECTION, 1), embed, n=cfg["n_vectors"], **kw)
    q = manifold(key_of(seed, 2), embed, n=cfg["n_queries"], **kw)
    ts = host_rng(seed, 3).permutation(cfg["n_vectors"]).astype(np.float32)
    return x, np.asarray(q), ts
