"""Adaptive query-time routing (hub-aware probing + per-query early
termination) — the deterministic suite.

Three layers of guarantees:

1. **Bit-identity**: ``adaptive=False`` (the default) never touches the
   adaptive machinery, and ``probe_margin=inf`` short-circuits to the
   static dispatch host-side, so both are bit-identical to the pre-PR
   static plane on every backend, tier and mode.  A huge FINITE margin at
   exhaustive knobs exercises the genuinely ragged path (invalid probes
   killed + stable-partitioned, bucketed re-dispatch) yet must still
   return the exact static result.
2. **Stopping-rule unit contract** (``routing.adaptive_prefix``): the
   distance-gap rule, the hub-set always-probed invariant, the
   ``min_probes`` floor, invalid-grain kills, and the stable partition.
3. **Traffic plumbing**: routing-win / touch counters accumulate only
   under adaptive search and surface through ``grain_health`` /
   ``hub_grains`` / ``probe_stats``; the hub set derived from them is
   probed by every query end-to-end.

The randomized twin (any mutation interleaving, same huge-finite-margin
trick, vs the brute-force oracle) is
test_core_properties.test_adaptive_mutation_interleaving_matches_bruteforce;
the seeded always-on sweep of that oracle lives here.  The forced-4-device
sharded identity twin runs in test_store_sharded.py.
"""
import numpy as np
import pytest

import mutation_property
from repro.core import HNTLConfig, planner, routing
from repro.core.store import VectorStore
from repro.core.types import BIG

D, SEG_ROWS, N_SEG = 24, 128, 3

# compiled kernels need TPU; on CPU their kernel bodies run in interpreter
# mode, asked for by name (same registry rule as test_scan_plane.py)
BACKENDS = ["ref", "interpret", "fused_interpret", "fused_ref"]
CASCADES = ["cascade_interpret", "cascade_ref"]


def _cfg():
    return HNTLConfig(d=D, k=6, s=0, n_grains=4, nprobe=4, pool=32,
                      block=32, hub_size=2)


def _build(cold: bool):
    rng = np.random.default_rng(11)
    st = VectorStore(_cfg(), seal_threshold=SEG_ROWS, cold_tier=cold)
    x = rng.standard_normal((N_SEG * SEG_ROWS, D)).astype(np.float32)
    for i in range(N_SEG):
        st.add(x[i * SEG_ROWS:(i + 1) * SEG_ROWS],
               tags=[1 << (i % 3)] * SEG_ROWS, ts=[float(i)] * SEG_ROWS)
    assert st.n_segments == N_SEG and not st._mem
    q = (x[:5] + 0.01 * rng.standard_normal((5, D))).astype(np.float32)
    return st, x, q


def _exhaustive(st):
    return dict(nprobe=sum(s.index.grains.n_grains for s in st._segments),
                pool=st.n_vectors * 2)


@pytest.fixture(scope="module", params=["warm", "cold"])
def store(request):
    return _build(request.param == "cold")


def _assert_same(res, ref, exact_dists: bool = False):
    assert np.array_equal(np.asarray(res.ids, np.int64),
                          np.asarray(ref.ids, np.int64))
    if exact_dists:
        np.testing.assert_array_equal(np.asarray(res.dists),
                                      np.asarray(ref.dists))
    else:
        np.testing.assert_allclose(np.asarray(res.dists),
                                   np.asarray(ref.dists),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# bit-identity: off / inf short-circuit / huge finite margin
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["A", "B"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_margin_inf_bit_identical_to_static(store, backend, mode):
    """probe_margin=inf is resolved HOST-side before tracing, so the
    dispatch (and its jit cache key) is the static plane's — results must
    be bit-identical, dists included."""
    st, x, q = store
    ref = st.search(q, topk=5, mode=mode, scan_impl=backend)
    res = st.search(q, topk=5, mode=mode, scan_impl=backend,
                    adaptive=True, probe_margin=float("inf"))
    _assert_same(res, ref, exact_dists=True)


@pytest.mark.parametrize("mode", ["A", "B"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_huge_margin_exhaustive_identity(store, backend, mode):
    """A huge FINITE margin runs the real ragged machinery — invalid
    probes killed, stable partition, per-width bucketed re-dispatch — but
    at exhaustive knobs every valid grain stays active, so the result
    still equals the static exhaustive plane exactly."""
    st, x, q = store
    kw = dict(topk=5, mode=mode, scan_impl=backend, **_exhaustive(st))
    ref = st.search(q, **kw)
    res = st.search(q, adaptive=True, probe_margin=1e30, **kw)
    _assert_same(res, ref)


@pytest.mark.parametrize("impl", CASCADES)
def test_huge_margin_cascade_identity(store, impl):
    """The ragged probe vector threads through the staged cascade too:
    budgets >= pool at exhaustive knobs must still be exact."""
    st, x, q = store
    ex = _exhaustive(st)
    kw = dict(topk=5, mode="B", scan_impl=impl,
              budgets=(ex["pool"], ex["pool"]), **ex)
    _assert_same(st.search(q, adaptive=True, probe_margin=1e30, **kw),
                 st.search(q, **kw))


@pytest.mark.parametrize("filt", [dict(tag_mask=2),
                                  dict(ts_range=(0.0, 2.0))])
def test_huge_margin_identity_under_predicates(store, filt):
    """Filter pushdown masks grains to BIG in routing; the stopping rule
    must kill exactly those probes and no live ones."""
    st, x, q = store
    kw = dict(topk=5, mode="B", **_exhaustive(st), **filt)
    _assert_same(st.search(q, adaptive=True, probe_margin=1e30, **kw),
                 st.search(q, **kw))


def test_adaptive_recall_by_construction_seeded():
    """Seeded always-on sweep of the adaptive mutation-interleaving
    oracle (the hypothesis fuzz twin lives in test_core_properties):
    through add/seal/delete/upsert/compact/maintain, adaptive search with
    a huge finite margin still equals brute force exactly."""
    for ops, seed, cold in [
            (("add", "seal", "delete", "upsert", "seal"), 5, False),
            (("seal", "delete", "maintain", "add", "compact"), 9, True),
            (("add", "add", "seal", "seal", "delete", "maintain"), 17,
             False)]:
        mutation_property.mutation_interleaving_check(
            ops, seed, cold, adaptive_margin=1e30)


# ---------------------------------------------------------------------------
# stopping-rule unit contract (routing.adaptive_prefix)
# ---------------------------------------------------------------------------


def _prefix(gd2, margin, **kw):
    import jax.numpy as jnp
    gd2 = np.asarray(gd2, np.float32)
    gids = np.tile(np.arange(gd2.shape[1], dtype=np.int32),
                   (gd2.shape[0], 1))
    if kw.get("hub_mask") is not None:
        kw["hub_mask"] = jnp.asarray(kw["hub_mask"])
    g, n = routing.adaptive_prefix(jnp.asarray(gids), jnp.asarray(gd2),
                                   margin=margin, **kw)
    return np.asarray(g), np.asarray(n)


def test_distance_gap_rule_and_stable_partition():
    """Probes within (1+margin)x the lead distance stay; others are
    killed and moved BEHIND the survivors with relative order kept."""
    g, n = _prefix([[1.0, 1.5, 10.0, 12.0]], margin=1.0)
    assert n.tolist() == [2]                      # 1.5 <= 2.0, 10 > 2.0
    assert g[0].tolist() == [0, 1, 2, 3]          # stable partition
    g, n = _prefix([[1.0, 5.0, 1.8, 6.0]], margin=1.0)
    assert n.tolist() == [2]
    assert g[0].tolist() == [0, 2, 1, 3]          # survivors first, in order


def test_hub_always_probed():
    """A hub grain far outside the distance-gap margin is still active —
    the always-probed invariant the hub set exists for."""
    hub = np.zeros(4, bool)
    hub[2] = True                                 # grain 2 == probe 2 below
    g, n = _prefix([[1.0, 1.5, 50.0, 60.0]], margin=1.0, hub_mask=hub)
    assert n.tolist() == [3]
    assert g[0].tolist() == [0, 1, 2, 3]
    # ...but a hub cannot revive an INVALID (masked/empty) grain
    g, n = _prefix([[1.0, 1.5, BIG, 60.0]], margin=1.0, hub_mask=hub)
    assert n.tolist() == [2]


def test_min_probes_floor():
    """The first min_probes probes always stay active (tail-recall
    floor), and n_active never drops below 1 even when everything else
    is killed."""
    g, n = _prefix([[1.0, 50.0, 60.0, 70.0]], margin=0.0, min_probes=3)
    assert n.tolist() == [3]
    g, n = _prefix([[BIG, BIG, BIG, BIG]], margin=0.0)
    assert n.tolist() == [1]                      # kernel masks BIG anyway


def test_invalid_grains_killed():
    """BIG-distance probes (masked or empty grains) are killed even when
    they sit inside the margin window arithmetically."""
    g, n = _prefix([[1.0, BIG, 1.5, BIG]], margin=1.0)
    assert n.tolist() == [2]
    assert g[0].tolist() == [0, 2, 1, 3]


def test_per_query_independence():
    """Each query's prefix depends only on its own row."""
    g, n = _prefix([[1.0, 1.2, 9.0, 9.5],
                    [1.0, 9.0, 9.2, 9.5]], margin=0.5)
    assert n.tolist() == [2, 1]


# ---------------------------------------------------------------------------
# validation: one actionable error at submit time
# ---------------------------------------------------------------------------


def test_check_probe_args_errors():
    with pytest.raises(ValueError, match="adaptive=True"):
        routing.check_probe_args(False, 0.5)
    with pytest.raises(ValueError, match=">= 0"):
        routing.check_probe_args(True, float("nan"))
    with pytest.raises(ValueError, match=">= 0"):
        routing.check_probe_args(True, -0.1)
    with pytest.raises(ValueError, match="min_probes"):
        routing.check_probe_args(True, 0.5, 0)
    with pytest.raises(ValueError, match="min_probes"):
        routing.check_probe_args(True, 0.5, True)
    routing.check_probe_args(True, float("inf"), 2)     # inf is legal


def test_search_rejects_bad_adaptive_combinations(store):
    st, x, q = store
    with pytest.raises(ValueError, match="adaptive=True"):
        st.search(q, topk=5, probe_margin=0.5)
    with pytest.raises(ValueError, match="fused"):
        st.search(q, topk=5, adaptive=True, fused=False)
    with pytest.raises(ValueError, match="global"):
        st.search(q, topk=5, adaptive=True, route_mode="per_segment")


# ---------------------------------------------------------------------------
# traffic counters, hub set, health surfacing
# ---------------------------------------------------------------------------


def test_traffic_accumulates_only_under_adaptive():
    st, x, q = _build(False)
    st.search(q, topk=5, mode="B")                # static: no traffic
    assert st.probe_stats() == {"queries": 0, "active_probes": 0,
                                "mean_active": 0.0}
    assert st.hub_grains().size == 0
    assert all((h["route_wins"] == 0).all() and (h["touches"] == 0).all()
               for h in st.grain_health())

    st.search(q, topk=5, mode="B", adaptive=True, probe_margin=0.5)
    stats = st.probe_stats()
    assert stats["queries"] == q.shape[0]
    assert stats["active_probes"] >= q.shape[0]   # n_active >= 1 each
    assert stats["mean_active"] >= 1.0

    health = st.grain_health()
    wins = np.concatenate([h["route_wins"] for h in health])
    touches = np.concatenate([h["touches"] for h in health])
    assert wins.sum() == q.shape[0]               # one routing win / query
    assert touches.sum() == stats["active_probes"]

    hubs = st.hub_grains()
    assert 0 < hubs.size <= st.cfg.hub_size


def test_hub_set_probed_by_every_query_end_to_end():
    """Integration form of the always-probed invariant: with the hub set
    accumulated from real traffic, every query's active prefix contains
    every valid hub grain even at margin=0 (which would otherwise keep
    only the lead grain)."""
    import jax.numpy as jnp
    st, x, q = _build(False)
    st.search(q, topk=5, mode="B", adaptive=True, probe_margin=0.5)
    hubs = st.hub_grains()
    assert hubs.size > 0
    man = st.snapshot()
    entry = st._stacked_for(man.segments, None)
    stacked = st._live_plane(entry, man, st._clock())
    traffic = st._traffic_for(man.segments, stacked.index.routing.n_grains)
    hub = st._hub_mask_host(traffic)
    nprobe = sum(s.index.grains.n_grains for s in st._segments)
    gids, n_active, _, _ = planner.probe_plan(
        stacked, jnp.asarray(q), nprobe=nprobe, probe_margin=0.0,
        min_probes=1, hub_mask=jnp.asarray(hub))
    gids, n_active = np.asarray(gids), np.asarray(n_active)
    for qi in range(q.shape[0]):
        active = set(gids[qi, :n_active[qi]].tolist())
        assert set(hubs.tolist()) <= active, (qi, hubs, active)


def test_probe_traffic_cache_is_bounded():
    """Traffic entries are LRU-bounded like the plane cache, so a stream
    of segment-set epochs cannot grow host memory without bound."""
    st, x, q = _build(False)
    st.search(q[:1], topk=3, mode="B", adaptive=True, probe_margin=0.5)
    limit = max(4, st.stack_cache_entries)
    for _ in range(limit + 3):                    # fake segment-set epochs
        st._traffic_for((object(),), 4)
    assert len(st._probe_traffic) <= limit


# ---------------------------------------------------------------------------
# tenancy composition
# ---------------------------------------------------------------------------


def test_tenant_coalesced_adaptive_identity():
    """Coalesced multi-tenant retrieval: inf short-circuits to the static
    coalesced dispatch bit-for-bit; a huge finite margin at exhaustive
    knobs runs the ragged path on the per-query tenant-masked routing
    pass and must still match exactly."""
    from repro.serve.tenancy import (RetrievalRequest, TenantRegistry,
                                     coalesced_retrieve)
    rng = np.random.default_rng(3)
    cfg = HNTLConfig(d=16, k=4, s=0, n_grains=2, nprobe=2, pool=32,
                     block=16, envelope_frac=1.0)
    base = VectorStore(cfg, seal_threshold=64)
    base.add(rng.standard_normal((96, 16)).astype(np.float32))
    reg = TenantRegistry(base, memtable_budget=32)
    for t in range(3):
        reg.get(f"t{t}").add(
            rng.standard_normal((8, 16)).astype(np.float32))
    qs = rng.standard_normal((6, 16)).astype(np.float32)

    def run(**kw):
        reqs = [RetrievalRequest(rid=i, tenant=f"t{i % 3}", q=qs[i],
                                 topk=4, mode="B") for i in range(6)]
        coalesced_retrieve(reg, reqs, **kw)
        return reqs

    ex = dict(nprobe=8, pool=256)
    for a, b in [(run(**ex), run(adaptive=True,
                                 probe_margin=float("inf"), **ex)),
                 (run(**ex), run(adaptive=True, probe_margin=1e30, **ex))]:
        for ra, rb in zip(a, b):
            assert np.array_equal(np.asarray(ra.result.ids),
                                  np.asarray(rb.result.ids)), ra.rid
            np.testing.assert_allclose(np.asarray(ra.result.dists),
                                       np.asarray(rb.result.dists),
                                       rtol=1e-5, atol=1e-5)


def test_engine_validates_adaptive_flags():
    """ServeEngine applies the same submit-time validation as the store:
    a bad knob combination fails at engine construction, not on the first
    retrieval three layers down."""
    import types as _t

    from repro.serve.engine import ServeEngine
    dummy = _t.SimpleNamespace(cfg=None)
    with pytest.raises(ValueError, match="adaptive=True"):
        ServeEngine(dummy, None, probe_margin=0.25)
    with pytest.raises(ValueError, match="min_probes"):
        ServeEngine(dummy, None, adaptive=True, min_probes=0)
