"""Multi-tenant serving plane conformance: isolation, coalescing, budgets.

Strategy: every coalesced result must be *bit-identical* to the same
tenant's solo dispatch (same knobs) — tenancy is pure masking, ANDed after
identical arithmetic, so fusing many tenants into one padded dispatch may
never perturb any individual request.  Isolation is checked both ways:
bit-for-bit vs per-tenant solo stores AND semantically vs brute-force
per-tenant oracles (shared-gid deletes/upserts, TTL, filters).  Registry
lifecycle (memtable budget -> forced seal, LRU freeze/thaw, manifest
validity across eviction) and the engine's request validation round it
out.  Forced-multi-device sharded twins run in a subprocess with 8 host
devices (the main process keeps the 1-device view, per conftest).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import HNTLConfig
from repro.core.store import VectorStore
from repro.serve.engine import ServeEngine
from repro.serve.tenancy import (RetrievalRequest, TenantRegistry,
                                 coalesced_retrieve)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
ROOT = os.path.join(os.path.dirname(__file__), "..")
D = 16
# every ScanPlane backend that runs on CPU (test_scan_plane.py contract)
BACKENDS = [None, "interpret", "fused_interpret", "fused_ref", "auto"]


def run_sub(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + os.path.dirname(__file__)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    return out.stdout


def _cfg():
    return HNTLConfig(d=D, k=4, s=0, n_grains=2, nprobe=2, pool=64,
                      block=16, envelope_frac=1.0)


def _base(n=96, cold=False, seed=0):
    rng = np.random.default_rng(seed)
    st = VectorStore(_cfg(), seal_threshold=32, cold_tier=cold,
                     clock=lambda: 0.0)
    st.add(rng.standard_normal((n, D)).astype(np.float32),
           tags=rng.integers(1, 4, size=n).tolist(),
           ts=rng.uniform(0.0, 10.0, size=n).tolist())
    return st, rng


def _exhaustive(reg):
    union = reg.union_segments()
    return dict(nprobe=max(sum(s.index.grains.n_grains for s in union), 1),
                pool=max(2 * sum(s.n for s in union) + 64, 1))


def _solo(reg, req, scan_impl=None, now=0.0, **knobs):
    st = reg.get(req.tenant)
    return st.search(req.q[None], topk=req.topk, mode=req.mode,
                     tag_mask=req.tag_mask, ts_range=req.ts_range,
                     scan_impl=scan_impl, now=now, **knobs)


def _assert_solo_parity(reg, reqs, scan_impl=None, now=0.0, **knobs):
    """Every coalesced result bit-identical to its tenant's solo search."""
    for r in reqs:
        assert r.done and r.result is not None
        solo = _solo(reg, r, scan_impl=scan_impl, now=now, **knobs)
        np.testing.assert_array_equal(np.asarray(r.result.ids),
                                      np.asarray(solo.ids)[0],
                                      err_msg=f"rid={r.rid} {r.tenant}")
        np.testing.assert_array_equal(np.asarray(r.result.dists),
                                      np.asarray(solo.dists)[0])


def _populate(reg, rng, names, n_priv=40):
    """Private writes per tenant: forces a seal (budget 16 < n_priv) and
    leaves memtable rows; plus a few private deletes."""
    own = {}
    for t, name in enumerate(names):
        st = reg.get(name)
        own[name] = st.add(
            (10.0 * (t + 1) + rng.standard_normal((n_priv, D))
             ).astype(np.float32),
            tags=rng.integers(1, 4, size=n_priv).tolist(),
            ts=rng.uniform(0.0, 10.0, size=n_priv).tolist())
        st.delete(own[name][:2])
    return own


def _window(rng, names, n=8, topk=5, mode="B", **kw):
    return [RetrievalRequest(
        rid=i, tenant=names[i % len(names)],
        q=rng.standard_normal(D).astype(np.float32), topk=topk, mode=mode,
        **kw) for i in range(n)]


# ---------------------------------------------------------------------------
# registry lifecycle
# ---------------------------------------------------------------------------


def test_branch_shares_segments_cow():
    base, rng = _base()
    reg = TenantRegistry(base, memtable_budget=16, max_live=4)
    n0 = base.n_segments
    a = reg.get("a")
    assert all(sa is sb for sa, sb in zip(a._segments, base._segments))
    a.add(rng.standard_normal((4, D)).astype(np.float32))
    a.seal()
    assert base.n_segments == n0                    # CoW: base untouched
    assert a.n_segments == n0 + 1                   # private seal


def test_budget_overflow_forces_seal_not_data_loss():
    base, rng = _base()
    reg = TenantRegistry(base, memtable_budget=8, max_live=4)
    st = reg.get("a")
    vecs = rng.standard_normal((30, D)).astype(np.float32)
    ids = st.add(vecs)
    assert st.n_segments > base.n_segments, "budget must force a seal"
    assert len(st._mem) < 8
    res = st.search(vecs, topk=1, mode="B", **_exhaustive(reg))
    np.testing.assert_array_equal(np.asarray(res.ids)[:, 0], ids)


def test_registry_arg_validation():
    base, _ = _base()
    with pytest.raises(ValueError):
        TenantRegistry(base, memtable_budget=0)
    with pytest.raises(ValueError):
        TenantRegistry(base, max_live=0)


def test_lru_eviction_bounds_live_and_thaws_bit_identical():
    base, rng = _base()
    reg = TenantRegistry(base, memtable_budget=16, max_live=2)
    own = _populate(reg, rng, ["a", "b"], n_priv=20)
    # seal both so freeze/thaw can't change the segment structure: the
    # before/after searches then share identical candidate selection even
    # at default (non-exhaustive) knobs
    reg.get("a").seal(), reg.get("b").seal()
    q = rng.standard_normal((2, D)).astype(np.float32)
    before = reg.get("a").search(q, topk=6, mode="B")
    reg.get("c")                     # evicts the LRU victim ("b" or "a")
    reg.get("d")
    assert reg.n_live == 2
    after = reg.get("a").search(q, topk=6, mode="B")   # thaw
    np.testing.assert_array_equal(np.asarray(before.ids),
                                  np.asarray(after.ids))
    np.testing.assert_array_equal(np.asarray(before.dists),
                                  np.asarray(after.dists))
    # private rows and tombstones survived the freeze/thaw cycle
    assert set(np.asarray(after.ids).ravel().tolist()) \
        - set(range(96)) - {-1} <= set(own["a"].tolist())


def test_explicit_evict_and_rehydration_state():
    base, rng = _base()
    reg = TenantRegistry(base, memtable_budget=16, max_live=4)
    st = reg.get("a")
    st.add(rng.standard_normal((4, D)).astype(np.float32))
    tag, epoch, nid = st._cold_tag, st._epoch, st._next_id
    assert reg.evict("a") is True
    assert reg.evict("a") is False           # already frozen
    assert reg.evict("nope") is False        # unknown
    st2 = reg.get("a")
    assert st2 is not st
    # writer identity + counters continue the SAME lineage: cached liveness
    # bitmaps keyed (writer, epoch) stay coherent across freeze/thaw
    assert st2._cold_tag == tag
    assert st2._epoch == epoch and st2._next_id == nid
    assert len(st2._mem) == 0                # freeze sealed the memtable


def test_evicted_tenants_manifest_stays_valid():
    base, rng = _base()
    reg = TenantRegistry(base, memtable_budget=16, max_live=4)
    st = reg.get("a")
    st.add(rng.standard_normal((6, D)).astype(np.float32))
    man = st.snapshot()
    q = rng.standard_normal((2, D)).astype(np.float32)
    before = st.search(q, topk=5, manifest=man)
    reg.evict("a")
    st2 = reg.get("a")                       # memtable now sealed
    after = st2.search(q, topk=5, manifest=man)   # pre-freeze manifest
    np.testing.assert_array_equal(np.asarray(before.ids),
                                  np.asarray(after.ids))


def test_union_segments_stable_under_lru_access_order():
    base, rng = _base()
    reg = TenantRegistry(base, memtable_budget=8, max_live=4)
    for n in ["a", "b", "c"]:
        reg.get(n).add(rng.standard_normal((10, D)).astype(np.float32))
    u1 = reg.union_segments()
    reg.get("c"), reg.get("a"), reg.get("b")       # churn LRU order
    u2 = reg.union_segments()
    assert all(x is y for x, y in zip(u1, u2)) and len(u1) == len(u2), \
        "union order must follow REGISTRATION order, not LRU access order" \
        " (a churning order would churn the plane cache key every window)"
    assert len({id(s) for s in u1}) == len(u1)     # identity-deduped


def test_run_maintenance_off_serving_path():
    base, rng = _base()
    reg = TenantRegistry(base, memtable_budget=8, max_live=4)
    st = reg.get("a")
    ids = st.add(rng.standard_normal((24, D)).astype(np.float32))
    st.delete(ids[:20])                      # rot a private segment
    rep = reg.run_maintenance(now=0.0)
    assert set(rep) == {"a"}
    reqs = _window(rng, ["a"], n=2)
    coalesced_retrieve(reg, reqs, **_exhaustive(reg))
    _assert_solo_parity(reg, reqs, **_exhaustive(reg))
    got = {int(i) for r in reqs for i in np.asarray(r.result.ids) if i >= 0}
    assert not (got & set(ids[:20].tolist())), "maintained plane resurrected"


# ---------------------------------------------------------------------------
# coalesced == solo parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["A", "B"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_coalesced_equals_solo_every_backend(mode, backend):
    base, rng = _base()
    reg = TenantRegistry(base, memtable_budget=16, max_live=4)
    _populate(reg, rng, ["a", "b", "c"])
    reqs = _window(rng, ["a", "b", "c"], n=9, mode=mode)
    kn = _exhaustive(reg)
    coalesced_retrieve(reg, reqs, scan_impl=backend, **kn)
    _assert_solo_parity(reg, reqs, scan_impl=backend, **kn)


def test_coalesced_equals_solo_default_knobs():
    """Default (non-exhaustive) knobs: routing must pick the same grains
    per query whether or not other tenants ride the batch."""
    base, rng = _base()
    reg = TenantRegistry(base, memtable_budget=16, max_live=4)
    _populate(reg, rng, ["a", "b"])
    reqs = _window(rng, ["a", "b"], n=6)
    coalesced_retrieve(reg, reqs)
    _assert_solo_parity(reg, reqs)


def test_cross_tenant_isolation_private_rows():
    base, rng = _base()
    reg = TenantRegistry(base, memtable_budget=16, max_live=4)
    own = _populate(reg, rng, ["a", "b"])
    # aim queries straight at the OTHER tenant's private cluster: nothing
    # of theirs may come back, even as the nearest vectors in the union
    reqs = [RetrievalRequest(rid=0, tenant="a",
                             q=np.full(D, 20.0, np.float32), topk=8,
                             mode="B"),
            RetrievalRequest(rid=1, tenant="b",
                             q=np.full(D, 10.0, np.float32), topk=8,
                             mode="B")]
    coalesced_retrieve(reg, reqs, **_exhaustive(reg))
    for r, other in zip(reqs, ["b", "a"]):
        got = {int(i) for i in np.asarray(r.result.ids) if i >= 0}
        priv = got - set(range(96))
        mine = set(own[r.tenant].tolist())
        assert priv <= mine, f"{r.tenant} leaked {sorted(priv - mine)[:4]}"


def _base_vecs(n=96, seed=0):
    """Like _base but keeps the raw vectors so tests can map gid -> vec
    (gids are assigned sequentially at add time)."""
    rng = np.random.default_rng(seed)
    vecs = rng.standard_normal((n, D)).astype(np.float32)
    st = VectorStore(_cfg(), seal_threshold=32, clock=lambda: 0.0)
    st.add(vecs)
    return st, rng, vecs


def test_shared_gid_delete_is_tenant_scoped():
    base, rng, vecs = _base_vecs()
    reg = TenantRegistry(base, memtable_budget=16, max_live=4)
    a, b = reg.get("a"), reg.get("b")
    a.delete([0, 1, 2])
    reqs = [RetrievalRequest(rid=0, tenant="a", q=vecs[0], topk=4,
                             mode="B"),
            RetrievalRequest(rid=1, tenant="b", q=vecs[0], topk=4,
                             mode="B")]
    coalesced_retrieve(reg, reqs, **_exhaustive(reg))
    ids_a = set(np.asarray(reqs[0].result.ids).tolist())
    ids_b = np.asarray(reqs[1].result.ids)
    assert not ({0, 1, 2} & ids_a), "tenant a must not see its deletes"
    assert ids_b[0] == 0, "tenant b still sees the shared row"


def test_shared_gid_upsert_shadows_only_in_writer():
    base, rng, vecs = _base_vecs()
    reg = TenantRegistry(base, memtable_budget=16, max_live=4)
    a, b = reg.get("a"), reg.get("b")
    orig = vecs[0]
    newv = (orig + 5.0).astype(np.float32)
    a.upsert([0], newv[None])
    reqs = [RetrievalRequest(rid=0, tenant="a", q=newv, topk=1, mode="B"),
            RetrievalRequest(rid=1, tenant="b", q=newv, topk=1, mode="B"),
            RetrievalRequest(rid=2, tenant="b", q=orig, topk=1, mode="B")]
    coalesced_retrieve(reg, reqs, **_exhaustive(reg))
    assert int(np.asarray(reqs[0].result.ids)[0]) == 0
    assert float(np.asarray(reqs[0].result.dists)[0]) < 1e-3, \
        "writer sees its NEW version"
    assert float(np.asarray(reqs[2].result.dists)[0]) < 1e-3, \
        "other tenant keeps the ORIGINAL version"
    _assert_solo_parity(reg, reqs, **_exhaustive(reg))


def test_filters_and_ttl_through_coalesce():
    base, rng = _base()
    reg = TenantRegistry(base, memtable_budget=16, max_live=4)
    st = reg.get("a")
    # tag 8: base tags are 1..3, so tag_mask=8 selects ONLY this batch
    ids = st.add(rng.standard_normal((8, D)).astype(np.float32),
                 tags=[8] * 8, ts=[5.0] * 8, ttl=100.0)
    st.seal()
    reqs = [RetrievalRequest(rid=0, tenant="a",
                             q=rng.standard_normal(D).astype(np.float32),
                             topk=5, mode="B", tag_mask=8),
            RetrievalRequest(rid=1, tenant="a",
                             q=rng.standard_normal(D).astype(np.float32),
                             topk=5, mode="B", ts_range=(4.0, 6.0))]
    kn = _exhaustive(reg)
    coalesced_retrieve(reg, reqs, now=0.0, **kn)
    _assert_solo_parity(reg, reqs, now=0.0, **kn)
    got = {int(i) for i in np.asarray(reqs[0].result.ids) if i >= 0}
    assert got and got <= set(ids.tolist()), got
    # TTL: at now=500 the batch is expired through the coalesced path too
    reqs2 = [RetrievalRequest(rid=0, tenant="a",
                              q=rng.standard_normal(D).astype(np.float32),
                              topk=5, mode="B", tag_mask=8)]
    coalesced_retrieve(reg, reqs2, now=500.0, **kn)
    assert (np.asarray(reqs2[0].result.ids) == -1).all()


def test_empty_store_returns_all_minus_one():
    st = VectorStore(_cfg(), seal_threshold=32, clock=lambda: 0.0)
    reg = TenantRegistry(st, memtable_budget=8, max_live=2)
    reqs = [RetrievalRequest(rid=0, tenant="ghost",
                             q=np.zeros(D, np.float32), topk=3, mode="B")]
    coalesced_retrieve(reg, reqs)
    assert (np.asarray(reqs[0].result.ids) == -1).all()
    assert reqs[0].done


def test_mixed_topk_and_mode_groups_one_batch():
    base, rng = _base()
    reg = TenantRegistry(base, memtable_budget=16, max_live=4)
    _populate(reg, rng, ["a", "b"])
    kn = _exhaustive(reg)
    reqs = [RetrievalRequest(rid=0, tenant="a",
                             q=rng.standard_normal(D).astype(np.float32),
                             topk=3, mode="A"),
            RetrievalRequest(rid=1, tenant="b",
                             q=rng.standard_normal(D).astype(np.float32),
                             topk=7, mode="B"),
            RetrievalRequest(rid=2, tenant="a",
                             q=rng.standard_normal(D).astype(np.float32),
                             topk=7, mode="B", tag_mask=1)]
    coalesced_retrieve(reg, reqs, **kn)
    _assert_solo_parity(reg, reqs, **kn)


@pytest.mark.parametrize("cold", [False, True])
def test_cold_tier_coalesced_parity(cold):
    base, rng = _base(cold=cold)
    reg = TenantRegistry(base, memtable_budget=16, max_live=4)
    _populate(reg, rng, ["a", "b"])
    reqs = _window(rng, ["a", "b"], n=6, mode="B")
    kn = _exhaustive(reg)
    coalesced_retrieve(reg, reqs, **kn)
    _assert_solo_parity(reg, reqs, **kn)


def test_batch_window_determinism_order_and_slicing():
    """The same request set must produce identical per-rid results no
    matter the arrival order or how the queue is sliced into windows."""
    base, rng = _base()
    reg = TenantRegistry(base, memtable_budget=16, max_live=4)
    _populate(reg, rng, ["a", "b", "c"])
    kn = _exhaustive(reg)

    def run(order, slices):
        reqs = _window(rng_q, ["a", "b", "c"], n=10)
        reqs = [reqs[i] for i in order]
        lo = 0
        for n in slices:
            coalesced_retrieve(reg, reqs[lo:lo + n], **kn)
            lo += n
        assert lo == len(reqs)
        return {r.rid: (np.asarray(r.result.ids).copy(),
                        np.asarray(r.result.dists).copy()) for r in reqs}

    rng_q = np.random.default_rng(3)
    ref = run(list(range(10)), [10])
    for order, slices in [
            (list(range(9, -1, -1)), [10]),          # reversed, one window
            (list(range(10)), [3, 3, 4]),            # sliced small
            ([7, 2, 9, 0, 5, 1, 8, 3, 6, 4], [1] * 10)]:  # shuffled, solo
        rng_q = np.random.default_rng(3)
        got = run(order, slices)
        for rid in ref:
            np.testing.assert_array_equal(ref[rid][0], got[rid][0],
                                          err_msg=f"rid={rid} {order}")
            np.testing.assert_array_equal(ref[rid][1], got[rid][1])


def test_padding_buckets_do_not_perturb():
    """Every batch size around the padding bucket boundaries (1..10 over
    bucket size 8) returns exactly the solo result — padding rows carry
    tenant_ix 0 but their results are discarded, never merged."""
    base, rng = _base()
    reg = TenantRegistry(base, memtable_budget=16, max_live=4)
    _populate(reg, rng, ["a", "b"])
    kn = _exhaustive(reg)
    for n in [1, 2, 7, 8, 9, 10]:
        reqs = _window(rng, ["a", "b"], n=n)
        coalesced_retrieve(reg, reqs, **kn)
        _assert_solo_parity(reg, reqs, **kn)


def test_zero_restacks_and_one_dispatch_per_group(plane_counters):
    base, rng = _base()
    reg = TenantRegistry(base, memtable_budget=16, max_live=4)
    _populate(reg, rng, ["a", "b"])
    coalesced_retrieve(reg, _window(rng, ["a", "b"], n=4))  # warm plane+jit

    stacks0 = plane_counters.stacks
    dispatches0 = plane_counters.dispatches
    snap = plane_counters.jit_snapshot()
    # 2 (mode, topk) groups x 3 windows: one dispatch per group per
    # window, zero re-stacks — per-tenant visibility is a mask, the union
    # plane is cached
    for _ in range(3):
        reqs = (_window(rng, ["a", "b"], n=5, topk=5, mode="B")
                + _window(rng, ["b", "a"], n=3, topk=3, mode="A"))
        for i, r in enumerate(reqs):
            r.rid = i
        coalesced_retrieve(reg, reqs)
    assert plane_counters.stacks == stacks0, \
        "coalesced hot path re-stacked the union plane"
    assert plane_counters.dispatches - dispatches0 == 6, (
        plane_counters.dispatches - dispatches0,
        "expected one dispatch per (mode, topk) group per window")
    # windows after the first never miss the jit cache either: the two
    # (mode, topk) groups compile on window 1, windows 2-3 are all hits
    assert plane_counters.compiles_since(snap)["search_stacked"] <= 2


# ---------------------------------------------------------------------------
# engine API
# ---------------------------------------------------------------------------


def _engine(reg):
    eng = ServeEngine.__new__(ServeEngine)
    eng.memory = reg.base
    eng.tenants = reg
    eng.memory_mesh = None
    eng.scan_impl = None
    return eng


def test_engine_validates_before_dispatch():
    base, rng = _base()
    reg = TenantRegistry(base, memtable_budget=16, max_live=4)
    eng = _engine(reg)
    q = np.zeros(D, np.float32)
    for bad in [dict(topk=0), dict(topk=-1), dict(topk=True),
                dict(topk="4"), dict(mode="Z"), dict(mode="b")]:
        with pytest.raises(ValueError):
            eng.retrieve(q, **bad)
    with pytest.raises(ValueError):
        eng.retrieve(np.zeros(D + 1, np.float32))      # d mismatch
    with pytest.raises(ValueError):
        eng.submit_retrieval(np.zeros((2, D), np.float32), tenant="a")
    no_mem = ServeEngine.__new__(ServeEngine)
    with pytest.raises(ValueError):
        no_mem.retrieve(q)
    no_ten = ServeEngine.__new__(ServeEngine)
    no_ten.memory = base
    with pytest.raises(ValueError):
        no_ten.retrieve(q, tenant="a")
    with pytest.raises(ValueError):
        no_ten.submit_retrieval(q, tenant="a")


def test_engine_empty_store_retrieval():
    st = VectorStore(_cfg(), seal_threshold=32, clock=lambda: 0.0)
    reg = TenantRegistry(st, memtable_budget=8, max_live=2)
    eng = _engine(reg)
    res = eng.retrieve(np.zeros(D, np.float32), topk=4, tenant="ghost")
    assert (np.asarray(res.ids) == -1).all()
    res2 = eng.retrieve(np.zeros(D, np.float32), topk=4)   # tenant-less
    assert (np.asarray(res2.ids) == -1).all()


def test_engine_tenant_retrieve_matches_solo():
    base, rng = _base()
    reg = TenantRegistry(base, memtable_budget=16, max_live=4)
    eng = _engine(reg)
    eng.remember(rng.standard_normal((6, D)).astype(np.float32), tenant="a")
    q = rng.standard_normal((2, D)).astype(np.float32)
    res = eng.retrieve(q, topk=5, tenant="a")
    solo = reg.get("a").search(q, topk=5, mode="B")
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(solo.ids))
    np.testing.assert_array_equal(np.asarray(res.dists),
                                  np.asarray(solo.dists))


def test_engine_submit_flush_windows():
    base, rng = _base()
    reg = TenantRegistry(base, memtable_budget=16, max_live=4)
    eng = _engine(reg)
    reqs = [eng.submit_retrieval(
        rng.standard_normal(D).astype(np.float32), tenant=f"t{i % 3}",
        topk=4) for i in range(7)]
    assert eng.flush_retrievals() == reqs           # returns the batch
    assert all(r.done for r in reqs)
    assert eng.flush_retrievals() == []                 # queue drained
    # max_batch slicing
    reqs2 = [eng.submit_retrieval(
        rng.standard_normal(D).astype(np.float32), tenant="t0", topk=4)
        for _ in range(5)]
    done = eng.flush_retrievals(max_batch=2)
    assert len(done) == 2 and all(r.done for r in done)
    assert not reqs2[2].done
    assert len(eng.flush_retrievals()) == 3
    # rids stay unique across windows
    rids = [r.rid for r in reqs + reqs2]
    assert len(set(rids)) == len(rids)


def test_engine_mutations_route_to_tenant():
    base, rng = _base()
    reg = TenantRegistry(base, memtable_budget=16, max_live=4)
    eng = _engine(reg)
    ids = eng.remember(rng.standard_normal((4, D)).astype(np.float32),
                       tenant="a")
    assert eng.evict(ids[:2], tenant="a") == 2
    newv = rng.standard_normal((1, D)).astype(np.float32)
    eng.refresh(ids[2:3], newv, tenant="a")
    res = eng.retrieve(newv[0], topk=1, tenant="a")
    assert int(np.asarray(res.ids)[0, 0]) == int(ids[2])
    # none of it leaked into the base store or another tenant
    assert base._live_seq == {}
    assert reg.get("b")._live_seq == {}


# ---------------------------------------------------------------------------
# property: per-tenant interleavings vs brute-force oracles
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st_h
    HAVE_HYP = True
except ImportError:                                    # pragma: no cover
    HAVE_HYP = False

if HAVE_HYP:
    import mutation_property

    @settings(deadline=None, max_examples=6)
    @given(ops=st_h.lists(
        st_h.tuples(st_h.sampled_from(mutation_property.TENANT_OPS),
                    st_h.integers(0, 3)),
        min_size=3, max_size=8),
        seed=st_h.integers(0, 2 ** 20), cold=st_h.booleans())
    def test_tenant_interleaving_matches_bruteforce(ops, seed, cold):
        """ANY interleaving of per-tenant add/delete/upsert/seal/evict over
        3 tenants (LRU max_live=2, so freeze/thaw is always exercised):
        each coalesced request returns exactly its own tenant's brute-force
        top-k.  Forced-4-device sharded twin below (subprocess)."""
        mutation_property.tenant_interleaving_check(ops, seed, cold)


# ---------------------------------------------------------------------------
# forced-multi-device sharded twins (subprocess, 8 host devices)
# ---------------------------------------------------------------------------


def test_sharded_coalesced_parity_subprocess():
    """Coalesced retrieval over a 4-way grain-sharded mesh == the fused
    single-device coalesced result bit-for-bit (warm + cold), and == each
    tenant's solo sharded search."""
    run_sub("""
        import numpy as np
        from repro.core import HNTLConfig
        from repro.core.store import VectorStore
        from repro.launch.mesh import make_search_mesh
        from repro.serve.tenancy import (RetrievalRequest, TenantRegistry,
                                         coalesced_retrieve)

        D = 16
        mesh = make_search_mesh(4)
        for cold in (False, True):
            rng = np.random.default_rng(0)
            base = VectorStore(HNTLConfig(d=D, k=4, s=0, n_grains=2,
                                          nprobe=2, pool=64, block=16,
                                          envelope_frac=1.0),
                               seal_threshold=32, cold_tier=cold,
                               clock=lambda: 0.0)
            base.add(rng.standard_normal((96, D)).astype(np.float32))
            reg = TenantRegistry(base, memtable_budget=16, max_live=4)
            for t, name in enumerate(["a", "b"]):
                st = reg.get(name)
                ids = st.add((10.0 * (t + 1)
                              + rng.standard_normal((40, D))
                              ).astype(np.float32))
                st.delete(ids[:2])
            union = reg.union_segments()
            kn = dict(nprobe=sum(s.index.grains.n_grains for s in union),
                      pool=2 * sum(s.n for s in union))

            def window():
                return [RetrievalRequest(
                    rid=i, tenant=["a", "b"][i % 2],
                    q=rng.standard_normal(D).astype(np.float32),
                    topk=5, mode="B") for i in range(6)]

            rng = np.random.default_rng(1)
            fused = window()
            coalesced_retrieve(reg, fused, **kn)
            rng = np.random.default_rng(1)
            shard = window()
            coalesced_retrieve(reg, shard, mesh=mesh, **kn)
            for f, s in zip(fused, shard):
                np.testing.assert_array_equal(
                    np.asarray(f.result.ids), np.asarray(s.result.ids),
                    err_msg=f"cold={cold} rid={f.rid}")
                np.testing.assert_allclose(
                    np.asarray(f.result.dists), np.asarray(s.result.dists),
                    rtol=1e-5, atol=1e-5)
                solo = reg.get(s.tenant).search(
                    s.q[None], topk=5, mode="B", mesh=mesh, now=0.0, **kn)
                np.testing.assert_array_equal(
                    np.asarray(s.result.ids), np.asarray(solo.ids)[0])
            print("cold" if cold else "warm", "sharded parity ok")
        """)


def test_sharded_tenant_property_subprocess():
    """The tenant-interleaving property on the 4-way sharded plane (same
    shared oracle as the in-process hypothesis wrapper)."""
    run_sub("""
        import numpy as np
        from mutation_property import tenant_interleaving_check, TENANT_OPS
        from repro.launch.mesh import make_search_mesh

        mesh = make_search_mesh(4)
        rng = np.random.default_rng(5)
        for trial in range(2):
            n = int(rng.integers(4, 8))
            ops = [(TENANT_OPS[int(rng.integers(len(TENANT_OPS)))],
                    int(rng.integers(4))) for _ in range(n)]
            tenant_interleaving_check(ops, seed=trial, cold=bool(trial),
                                      mesh=mesh)
            print("trial", trial, "ok")
        """)


# ---------------------------------------------------------------------------
# load benchmark gate (slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_serve_load_benchmark_with_latency_gate():
    """The serving-load benchmark's structural asserts (one dispatch per
    window, zero re-stacks, zero leaks, solo parity) plus the latency
    thresholds.  Slow-marked: CI runs it via benchmarks/run.py --quick
    without the latency gate; this is the full local check."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.serve_load", "--quick",
         "--assert-latency"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
