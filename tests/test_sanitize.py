"""Dynamic sanitizer contracts: zero recompiles + zero implicit transfers.

Two invariants the static rules can't prove are asserted at runtime:

1. **Zero-recompile** — ``store.search`` compiles exactly once per
   (manifest-shape, mesh, scan_impl, budgets) plane key.  Mutation
   epochs, ``maintain()`` identity passes, and tenant-coalesced windows
   swap pytree *leaves* (the liveness bitmap), never pytree *structure*
   or statics, so a warmed jit cache must never miss again.  Asserted
   through the shared ``plane_counters`` fixture (conftest), which reads
   the planner entry points' own compile caches.

2. **Zero implicit transfers** — the fused ("fused", "cascade") scan
   paths move nothing host<->device implicitly: queries arrive via an
   explicit ``jnp.asarray``, filter scalars via ``jax.device_put``, the
   final top-k leaves via ``jax.device_get``.  The cold tier's host
   memmap re-rank is the ONE sanctioned transfer point (pure-numpy
   gather on explicitly fetched candidate rows) and must stay legal
   under ``jax.transfer_guard("disallow")``.

``HNTL_SANITIZE=1`` additionally wraps every fused/sharded store search
in the same guard suite-wide (see conftest) — CI runs the forced-
multidevice job that way."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HNTLConfig
from repro.core.store import VectorStore

D, N_SEG, SEG_ROWS = 32, 3, 128


def _cfg():
    return HNTLConfig(d=D, k=8, s=4, n_grains=4, nprobe=4, pool=64,
                      block=32)


def _build(cold=False):
    rng = np.random.default_rng(7)
    st = VectorStore(_cfg(), seal_threshold=SEG_ROWS, cold_tier=cold,
                     clock=lambda: 1000.0)
    x = rng.standard_normal((N_SEG * SEG_ROWS, D)).astype(np.float32)
    for i in range(N_SEG):
        st.add(x[i * SEG_ROWS:(i + 1) * SEG_ROWS],
               tags=[1 << (i % 3)] * SEG_ROWS, ts=[float(i)] * SEG_ROWS)
    assert st.n_segments == N_SEG and not st._mem
    q = (x[:4] + 0.01 * rng.standard_normal((4, D))).astype(np.float32)
    return st, x, q


def _same(a, b):
    assert np.array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_allclose(np.asarray(a.dists), np.asarray(b.dists),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# 1. zero-recompile regression
# ---------------------------------------------------------------------------


def test_zero_recompiles_across_mutation_and_maintenance(plane_counters):
    """After one compile per plane key, the cache never misses again:

    - searches 1..n on an unmutated store: 1 compile (live leaf = None);
    - the FIRST post-mutation search: 1 more (live leaf None -> array is
      a pytree-structure change, a legitimate new cache entry);
    - every further mutation epoch (deletes, upserts, TTL clocks),
      maintain() identity passes, and repeated searches: 0 compiles,
      0 re-stacks — the zero-re-stack contract, asserted centrally."""
    st, x, q = _build()
    st.search(q, topk=5, mode="B")                 # compile: live=None
    st.search(q, topk=5, mode="B")                 # hit
    st.delete(np.arange(0, 10))
    st.search(q, topk=5, mode="B")                 # compile: live=array
    assert plane_counters.stacks == 1

    snap = plane_counters.jit_snapshot()
    stacks0 = plane_counters.stacks
    for epoch in range(3):                         # mutation epochs
        st.delete(np.arange(20 + 10 * epoch, 25 + 10 * epoch))
        st.search(q, topk=5, mode="B")
        st.upsert(np.arange(5) + 40, x[40:45] + 0.5)
        st.search(q, topk=5, mode="B")
    st.maintain()                                  # identity pass: healthy
    st.search(q, topk=5, mode="B")
    for _ in range(2):
        st.search(q, topk=5, mode="B")

    assert plane_counters.total_compiles_since(snap) == 0, \
        plane_counters.compiles_since(snap)
    assert plane_counters.stacks == stacks0, \
        "mutation/maintenance epoch re-stacked a healthy plane"


def test_distinct_plane_keys_compile_separately_then_hold(plane_counters):
    """scan_impl and budgets are part of the plane key: each combination
    compiles once, and re-searching any warmed combination is a hit."""
    st, _, q = _build()
    combos = [dict(scan_impl="fused_ref"),
              dict(scan_impl="cascade_ref", budgets=(64, 32))]
    for kw in combos:
        st.search(q, topk=5, mode="A", **kw)       # warm each key
    snap = plane_counters.jit_snapshot()
    for kw in combos:
        st.search(q, topk=5, mode="A", **kw)
    assert plane_counters.total_compiles_since(snap) == 0, \
        plane_counters.compiles_since(snap)


# ---------------------------------------------------------------------------
# 2. transfer-guard: fused scan paths move nothing implicitly
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kw", [
    dict(scan_impl="fused_ref"),
    dict(scan_impl="fused_interpret"),             # the Pallas kernel body
    dict(scan_impl="cascade_ref", budgets=(64, 32)),
    dict(scan_impl="cascade_interpret", budgets=(64, 32)),
], ids=["fused_ref", "fused", "cascade_ref", "cascade"])
def test_fused_scan_paths_zero_implicit_transfers(kw):
    st, _, q = _build()
    want = st.search(q, topk=5, mode="A", **kw)    # warm: compile outside
    with jax.transfer_guard("disallow"):
        got = st.search(q, topk=5, mode="A", **kw)
    _same(want, got)


def test_filter_scalars_are_explicitly_placed():
    """tag_mask/ts_range arrive as jax.device_put scalars — the pre-PR-8
    jnp.uint32(int) spelling was an implicit H2D and fails this guard."""
    st, _, q = _build()
    kw = dict(topk=5, mode="A", tag_mask=0b011, ts_range=(0.0, 2.0))
    want = st.search(q, **kw)
    with jax.transfer_guard("disallow"):
        got = st.search(q, **kw)
    _same(want, got)


def test_tenant_coalesced_dispatch_zero_implicit_transfers(monkeypatch):
    """The coalesced serving plane's per-query tenancy args (tenant_live
    [T, G, cap] stack + tenant_ix [Q]) are explicitly device_put — the
    pre-PR-8 ``jnp.asarray(tenant_ix, jnp.int32)`` spelling
    dtype-converted a host int64 array, an implicit H2D that failed the
    sanitized CI job; pinned here so plain tier-1 catches a regression
    too.  The guard wraps exactly what the HNTL_SANITIZE wrapper wraps —
    the fused dispatch, not the host-side merge epilogue around it."""
    from repro.serve.tenancy import (RetrievalRequest, TenantRegistry,
                                     coalesced_retrieve)
    st, x, q = _build()
    reg = TenantRegistry(st, memtable_budget=256, max_live=4)
    reg.get("a").delete(np.arange(4))              # per-tenant visibility
    reg.get("b")

    def window():
        return [RetrievalRequest(rid=i, tenant="ab"[i % 2], q=q[i % len(q)],
                                 topk=5, mode="A") for i in range(4)]

    want = coalesced_retrieve(reg, window())       # warm: compile outside
    orig = VectorStore._search_segments_fused

    def guarded(self, *a, **kw):
        with jax.transfer_guard("disallow"):
            return orig(self, *a, **kw)

    monkeypatch.setattr(VectorStore, "_search_segments_fused", guarded)
    got = coalesced_retrieve(reg, window())
    for w, g in zip(want, got):
        assert g.done
        np.testing.assert_array_equal(np.asarray(w.result.ids),
                                      np.asarray(g.result.ids))


def test_cold_rerank_is_the_sanctioned_transfer_point():
    """Mode B on a cold store re-ranks from host memmaps: candidate rows
    leave the device via explicit device_get, the exact re-rank is pure
    numpy, and nothing moves implicitly — the documented one transfer
    point stays guard-clean end to end."""
    st, _, q = _build(cold=True)
    kw = dict(topk=5, mode="B", scan_impl="fused_ref")
    want = st.search(q, **kw)
    with jax.transfer_guard("disallow"):
        got = st.search(q, **kw)
    _same(want, got)


def test_transfer_guard_semantics_canary():
    """The semantics the suite relies on (jax CPU backend): implicit H2D
    of a python/numpy scalar is blocked, explicit placement is not.  If a
    jax upgrade changes this, the sanitizer needs re-auditing."""
    with jax.transfer_guard("disallow"):
        jax.device_put(np.uint32(5))               # explicit: fine
        with pytest.raises(Exception):
            jnp.uint32(5)                          # implicit H2D: blocked


@pytest.mark.skipif(os.environ.get("HNTL_SANITIZE") != "1",
                    reason="sanitizer wrapper only installs under "
                           "HNTL_SANITIZE=1")
def test_sanitizer_wrapper_installed():
    assert getattr(VectorStore._search_segments_fused,
                   "_hntl_sanitized", False)
    assert getattr(VectorStore._search_segments_sharded,
                   "_hntl_sanitized", False)
