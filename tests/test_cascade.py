"""Mixed-precision cascade scan lockdown suite (tentpole PR 7).

Three layers of guarantees:

1. **int4 packing** — hypothesis properties of the nibble codec
   (``quantize.pack_int4``/``unpack_int4``): pack∘unpack is the
   clip-to-[-8, 7] identity, NaN packs as 0 (mirroring the fitters'
   NaN-exclusion), odd widths pad cleanly; plus the mixed-width blob
   serializer (``layout.pack_coords_blob``) round-trips bit-exactly and
   its byte accounting matches the per-grain widths.
2. **cascade conformance** — the "cascade"/"cascade_ref" ScanPlane
   backends produce results identical to "ref" through the REAL planes
   (``VectorStore.search`` over ``search_stacked`` and the forced-4-device
   ``search_stacked_sharded``) across warm/cold tiers, sketch on/off,
   fixed/density bit allocation, modes A/B, tag/ts/liveness predicates,
   tenant-coalesced vs solo dispatch, and after a maintenance epoch that
   re-tiers per-grain widths.  With ``budgets=None`` (and with exhaustive
   budgets) the cascade is lossless by construction — that is what makes
   bit-parity assertable.
3. **budget contract** — malformed / too-small stage budgets raise at
   validation time (store, planner, tenancy levels), budgets on a
   non-staged backend raise, and a fully-pruned pool comes back as all
   id -1 through both epilogue paths.
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HNTLConfig, build, scan_plane_names
from repro.core import index as index_mod
from repro.core import cascade, layout, planner, quantize, scanplane
from repro.core.store import VectorStore, stack_segments

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
D, SEG_ROWS, N_SEG = 24, 128, 2
# the kernel-backed cascade runs interpreted on CPU, asked for by name
CASCADES = ["cascade_interpret", "cascade_ref"]
CASES = [dict(), dict(tag_mask=2), dict(ts_range=(0.0, 1.0)),
         dict(tag_mask=1, ts_range=(0.0, 2.0))]


def _cfg(s: int, bit_alloc: str = "fixed") -> HNTLConfig:
    return HNTLConfig(d=D, k=6, s=s, n_grains=4, nprobe=4, pool=32,
                      block=32, bit_alloc=bit_alloc)


def _aniso(n: int, rng) -> np.ndarray:
    """Clustered low-rank data: density mode actually assigns int4."""
    c = rng.standard_normal((4, D)).astype(np.float32) * 4
    a = rng.integers(0, 4, n)
    b = rng.standard_normal((4, D, 3)).astype(np.float32)
    z = rng.standard_normal((n, 3)).astype(np.float32)
    x = c[a] + np.einsum("nk,ndk->nd", z, b[a])
    return (x + 0.01 * rng.standard_normal((n, D))).astype(np.float32)


def _build_store(cold: bool, s: int, bit_alloc: str):
    rng = np.random.default_rng(7)
    st = VectorStore(_cfg(s, bit_alloc), seal_threshold=SEG_ROWS,
                     cold_tier=cold)
    x = _aniso(N_SEG * SEG_ROWS, rng)
    for i in range(N_SEG):
        st.add(x[i * SEG_ROWS:(i + 1) * SEG_ROWS],
               tags=[1 << i] * SEG_ROWS, ts=[float(i)] * SEG_ROWS)
    assert st.n_segments == N_SEG and not st._mem
    q = (x[:4] + 0.01 * rng.standard_normal((4, D))).astype(np.float32)
    return st, x, q


@pytest.fixture(scope="module",
                params=[("warm", "fixed"), ("warm", "density"),
                        ("warm_sketch", "fixed"), ("warm_sketch", "density"),
                        ("cold", "fixed"), ("cold", "density")],
                ids=lambda p: f"{p[0]}-{p[1]}")
def store(request):
    tier, bit_alloc = request.param
    cold = tier == "cold"
    s = 4 if tier == "warm_sketch" else 0
    return _build_store(cold, s, bit_alloc)


def _assert_same(res, ref):
    assert np.array_equal(np.asarray(res.ids, np.int64),
                          np.asarray(ref.ids, np.int64))
    np.testing.assert_allclose(np.asarray(res.dists), np.asarray(ref.dists),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# conformance: cascade == ref through the stacked plane
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", CASCADES)
def test_cascade_parity_all_predicates(store, backend):
    st, x, q = store
    for case in CASES:
        ref = st.search(q, topk=5, mode="B", scan_impl="ref", **case)
        res = st.search(q, topk=5, mode="B", scan_impl=backend, **case)
        _assert_same(res, ref)


@pytest.mark.parametrize("backend", CASCADES)
def test_cascade_parity_mode_a_and_single_query(store, backend):
    st, x, q = store
    ref = st.search(q, topk=5, mode="A", scan_impl="ref")
    res = st.search(q, topk=5, mode="A", scan_impl=backend)
    _assert_same(res, ref)
    ref1 = st.search(q[:1], topk=3, mode="B", scan_impl="ref")
    res1 = st.search(q[:1], topk=3, mode="B", scan_impl=backend)
    _assert_same(res1, ref1)


@pytest.mark.parametrize("backend", CASCADES)
def test_cascade_parity_under_liveness(store, backend):
    """Tombstones ride stage 1's in-situ mask; deleted rows never
    resurface through any cascade stage."""
    st, x, q = store
    child = st.branch()
    victims = np.asarray(np.argsort(((x - q[:1]) ** 2).sum(1))[:3])
    child.delete(victims)
    ref = child.search(q, topk=5, mode="B", scan_impl="ref")
    res = child.search(q, topk=5, mode="B", scan_impl=backend)
    _assert_same(res, ref)
    assert not np.isin(victims, np.asarray(res.ids)).any()


@pytest.mark.parametrize("backend", CASCADES)
def test_budgeted_cascade_parity_when_exhaustive(store, backend):
    """budgets=(all slots, pool) prunes nothing: bit-identical to ref —
    the staged path is lossless whenever the budgets cover the pool."""
    st, x, q = store
    ref = st.search(q, topk=5, mode="B", scan_impl="ref")
    res = st.search(q, topk=5, mode="B", scan_impl=backend,
                    budgets=(4 * N_SEG * SEG_ROWS, 32))
    _assert_same(res, ref)


def test_cascade_never_gathers_probed_panels(store, monkeypatch):
    """Stage 1 streams through the select machinery and stage 2 gathers
    only [Q, b1, k] survivor columns — the [Q, P, k, cap] probed-panel
    copy must never exist."""
    st, x, q = store

    def poisoned(g, gids):
        raise AssertionError("cascade materialized coords[gids]")

    monkeypatch.setattr(planner, "_gather_probed_panels", poisoned)
    st.search(q, topk=7, mode="B", pool=39, scan_impl="cascade_ref")
    st.search(q, topk=7, mode="B", pool=39, scan_impl="cascade_interpret",
              budgets=(128, 16))


def test_cascade_parity_after_maintenance(store):
    """A maintenance epoch (deletes -> refit/merge, re-tiered widths under
    density) keeps every cascade backend identical to ref."""
    st, x, q = store
    child = st.branch()
    child.delete(np.arange(0, SEG_ROWS, 2))       # hollow out segment 0
    child.maintain()
    ref = child.search(q, topk=5, mode="B", scan_impl="ref")
    for backend in CASCADES:
        res = child.search(q, topk=5, mode="B", scan_impl=backend)
        _assert_same(res, ref)


# ---------------------------------------------------------------------------
# density bit allocation: build-time widths + maintenance re-tiering
# ---------------------------------------------------------------------------


def _easy_hard_store():
    """Two well-separated clusters: one rank-2 (easy -> int4) + a few
    low-variance isotropic rows hiding in it, one isotropic (hard ->
    int8).  Deleting the easy cluster's structured rows leaves isotropic
    survivors, so a maintenance refit must RE-TIER the grain to int8."""
    rng = np.random.default_rng(11)
    cfg = HNTLConfig(d=D, k=6, s=0, n_grains=2, nprobe=2, pool=64,
                     block=16, envelope_frac=1.0, bit_alloc="density")
    st = VectorStore(cfg, seal_threshold=128)
    b = rng.standard_normal((D, 2)).astype(np.float32)
    easy = (10.0 + rng.standard_normal((48, 2)).astype(np.float32) @ b.T)
    hiding = 10.0 + 0.1 * rng.standard_normal((16, D)).astype(np.float32)
    hard = -10.0 + rng.standard_normal((64, D)).astype(np.float32)
    x = np.concatenate([easy, hiding, hard]).astype(np.float32)
    st.add(x)
    st.seal()
    return st, x


def test_density_build_assigns_widths():
    st, x = _easy_hard_store()
    (seg,) = st.snapshot().segments
    qm = np.asarray(seg.index.grains.qmaxg)
    assert sorted(qm.tolist()) == [quantize.INT4_QMAX, quantize.INT8_QMAX]
    # fixed mode on the same data records no per-grain widths at all
    st2 = VectorStore(HNTLConfig(d=D, k=6, s=0, n_grains=2, nprobe=2,
                                 pool=64, block=16), seal_threshold=128)
    st2.add(x)
    st2.seal()
    assert st2.snapshot().segments[0].index.grains.qmaxg is None


def test_maintenance_retiers_drifted_grain():
    """Delete the structured rows: the easy grain's survivors are
    isotropic, the refit captures ~k/d < threshold, and the re-encode
    pass must climb the grain back to int8 — recorded in qmaxg."""
    st, x = _easy_hard_store()
    st.delete(np.arange(48))                      # the rank-2 rows
    rep = st.maintain()
    assert rep.changed and rep.total("refits") >= 1
    (seg,) = st.snapshot().segments
    qm = np.asarray(seg.index.grains.qmaxg)
    assert (qm == quantize.INT8_QMAX).all(), qm
    # and the repaired mixed-width store still scans at parity
    q = (x[48:52] + 0.01).astype(np.float32)
    ref = st.search(q, topk=5, mode="B", scan_impl="ref")
    for backend in CASCADES:
        _assert_same(st.search(q, topk=5, mode="B", scan_impl=backend), ref)


def test_stacked_and_looped_planes_carry_widths(store):
    """qmaxg fuses onto the stacked plane exactly when density; the legacy
    looped plane reads the same per-segment widths (parity incl. the
    per-grain envelope/quantize query path)."""
    st, x, q = store
    stk = stack_segments(st.snapshot().segments)
    if st.cfg.bit_alloc == "density":
        qm = np.asarray(stk.index.grains.qmaxg)
        assert qm.shape == (stk.index.grains.n_grains,)
        assert set(qm.tolist()) <= {quantize.INT4_QMAX, quantize.INT8_QMAX}
    else:
        assert stk.index.grains.qmaxg is None
    ref = st.search(q, topk=5, mode="B", scan_impl="ref")
    res = st.search(q, topk=5, mode="B", scan_impl="ref", fused=False)
    _assert_same(res, ref)


# ---------------------------------------------------------------------------
# forced-4-device sharded + tenant-coalesced conformance (subprocess)
# ---------------------------------------------------------------------------


def _run_sub(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC + os.pathsep + os.path.dirname(__file__)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    return out.stdout


@pytest.mark.parametrize("bit_alloc", ["fixed", "density"])
def test_sharded_parity_forced_4_devices(bit_alloc):
    """Grain-sharded plane: per-grain widths shard like every grain panel
    (SEARCH_PLANE_AXES) and both cascade backends — budgeted and not —
    stay identical to the sharded ref plane, masked and with tombstones."""
    out = _run_sub(f"""
        import numpy as np
        from test_cascade import _build_store, _assert_same, CASCADES
        from repro.launch.mesh import make_search_mesh
        for cold, s in ((False, 4), (True, 0)):
            st, x, q = _build_store(cold, s, {bit_alloc!r})
            st.delete(np.arange(5))
            mesh = make_search_mesh(4)
            for case in (dict(), dict(tag_mask=2),
                         dict(ts_range=(0.0, 1.0))):
                ref = st.search(q, topk=5, mode="B", scan_impl="ref",
                                mesh=mesh, **case)
                for backend in CASCADES:
                    res = st.search(q, topk=5, mode="B", scan_impl=backend,
                                    mesh=mesh, **case)
                    _assert_same(res, ref)
            ref0 = st.search(q, topk=5, mode="B", scan_impl="ref", mesh=mesh)
            resb = st.search(q, topk=5, mode="B",
                             scan_impl="cascade_interpret",
                             mesh=mesh, budgets=(4096, 32))
            _assert_same(resb, ref0)
        print("OK")
    """)
    assert "OK" in out


def test_sharded_recall_by_construction_forced_4_devices():
    """The mutation-interleaving oracle's cascade twin on the sharded
    plane: budgets=(pool, pool) over any interleaving still equals
    brute-force exactly (fixed ops list; the randomized in-process twin
    is the hypothesis test below)."""
    out = _run_sub("""
        import mutation_property
        from repro.launch.mesh import make_search_mesh
        mesh = make_search_mesh(4)
        ops = ("add", "seal", "delete", "add", "seal", "maintain", "upsert")
        for ba in ("fixed", "density"):
            mutation_property.mutation_interleaving_check(
                ops, seed=3, cold=False, mesh=mesh,
                scan_impl="cascade_ref", budgeted=True, bit_alloc=ba)
        print("OK")
    """)
    assert "OK" in out


def test_tenant_coalesced_equals_solo_cascade():
    """Coalesced multi-tenant retrieval with the budgeted cascade equals
    each tenant's solo dispatch (same backend, same budgets)."""
    from repro.serve.tenancy import (RetrievalRequest, TenantRegistry,
                                     coalesced_retrieve)
    rng = np.random.default_rng(3)
    cfg = HNTLConfig(d=16, k=4, s=0, n_grains=2, nprobe=2, pool=32,
                     block=16, envelope_frac=1.0, bit_alloc="density")
    base = VectorStore(cfg, seal_threshold=64)
    base.add(rng.standard_normal((96, 16)).astype(np.float32))
    reg = TenantRegistry(base, memtable_budget=32)
    for t in range(3):
        reg.get(f"t{t}").add(
            rng.standard_normal((8, 16)).astype(np.float32))
    qs = rng.standard_normal((6, 16)).astype(np.float32)
    reqs = [RetrievalRequest(rid=i, tenant=f"t{i % 3}", q=qs[i], topk=4,
                             mode="B") for i in range(6)]
    coalesced_retrieve(reg, reqs, scan_impl="cascade_ref",
                       budgets=(64, 16), nprobe=8, pool=64)
    for i, r in enumerate(reqs):
        solo = reg.get(r.tenant).search(
            qs[i], topk=4, mode="B", scan_impl="cascade_ref",
            budgets=(64, 16), nprobe=8, pool=64)
        assert np.array_equal(np.asarray(r.result.ids),
                              np.asarray(solo.ids)[0]), i
        np.testing.assert_allclose(np.asarray(r.result.dists),
                                   np.asarray(solo.dists)[0],
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# budget contract: validation errors + degraded pools
# ---------------------------------------------------------------------------


def test_budget_validation_errors(store):
    st, x, q = store
    with pytest.raises(ValueError, match="< topk"):
        st.search(q, topk=5, scan_impl="cascade_ref", budgets=(64, 2))
    with pytest.raises(ValueError, match="b1 >= b2"):
        st.search(q, topk=5, scan_impl="cascade_ref", budgets=(8, 64))
    with pytest.raises(ValueError, match="b1, b2"):
        st.search(q, topk=5, scan_impl="cascade_ref", budgets=(64,))
    with pytest.raises(ValueError, match="not staged"):
        st.search(q, topk=5, scan_impl="fused_ref", budgets=(64, 8))
    with pytest.raises(ValueError, match="fused search plane"):
        st.search(q, topk=5, scan_impl="cascade_ref", budgets=(64, 8),
                  fused=False)


def test_budget_validation_at_planner_level():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((96, D)).astype(np.float32)
    cfg = _cfg(0)
    idx, _ = build(x, cfg)
    with pytest.raises(ValueError, match="< topk"):
        planner.search(idx, jnp.asarray(x[:2]), nprobe=2, pool=16, topk=8,
                       scan_impl="cascade_ref", budgets=(16, 4))
    with pytest.raises(ValueError, match="not staged"):
        planner.search(idx, jnp.asarray(x[:2]), nprobe=2, pool=16, topk=4,
                       scan_impl="ref", budgets=(16, 8))
    # direct check_budgets contract
    cascade.check_budgets(None, 10)               # None is always fine
    cascade.check_budgets((8, 8), 8)
    with pytest.raises(ValueError):
        cascade.check_budgets((0, 0), 1)


def test_budget_validation_at_tenancy_level():
    from repro.serve.tenancy import (RetrievalRequest, TenantRegistry,
                                     coalesced_retrieve)
    rng = np.random.default_rng(4)
    base = VectorStore(HNTLConfig(d=16, k=4, s=0, n_grains=2, nprobe=2,
                                  pool=32, block=16), seal_threshold=64)
    base.add(rng.standard_normal((64, 16)).astype(np.float32))
    reg = TenantRegistry(base)
    req = RetrievalRequest(rid=0, tenant="t0",
                           q=rng.standard_normal(16).astype(np.float32),
                           topk=8, mode="B")
    with pytest.raises(ValueError, match="< topk"):
        coalesced_retrieve(reg, [req], scan_impl="cascade_ref",
                           budgets=(32, 4))


@pytest.mark.parametrize("mode", ["A", "B"])
@pytest.mark.parametrize("backend", CASCADES)
def test_fully_pruned_pool_returns_all_minus_one(mode, backend):
    """A pool with every slot pruned in stage 1 must come back all id -1
    through BOTH epilogue paths — with and without stage budgets."""
    rng = np.random.default_rng(1)
    x = rng.standard_normal((96, D)).astype(np.float32)
    cfg = _cfg(0)
    idx, _ = build(x, cfg)
    em = jnp.zeros((idx.grains.n_grains, idx.grains.cap), bool)
    res = index_mod.search(idx, x[:3], cfg, topk=4, mode=mode,
                           scan_impl=backend, extra_mask=em)
    assert (np.asarray(res.ids) == -1).all()
    assert (np.asarray(res.dists) >= planner.BIG / 2).all()
    st = VectorStore(cfg, seal_threshold=96)
    st.add(x, tags=[1] * 96)
    res2 = st.search(x[:3], topk=4, mode=mode, tag_mask=8,
                     scan_impl=backend, budgets=(64, 16))
    assert (np.asarray(res2.ids) == -1).all()


def test_registry_staged_flags():
    names = scan_plane_names()
    assert "cascade" in names and "cascade_ref" in names
    for n in CASCADES:
        p = scanplane.get_scan_plane(n)
        assert p.kind == scanplane.SELECT and p.staged
    assert not scanplane.get_scan_plane("fused").staged
    assert not scanplane.get_scan_plane("ref").staged


# ---------------------------------------------------------------------------
# int4 codec + mixed-width blob properties
#
# Each property runs twice: a deterministic seeded sweep (always on, so the
# codec is exercised even where hypothesis isn't installed) and a hypothesis
# fuzz twin (skipped gracefully without it — matching test_core_properties).
# ---------------------------------------------------------------------------

import mutation_property  # noqa: E402

try:
    from hypothesis import given, settings, strategies as hst
    HAVE_HYP = True
except ImportError:                                       # pragma: no cover
    HAVE_HYP = False


def _check_int4_roundtrip(n: int, seed: int):
    """unpack(pack(q), n) == clip(q, -8, 7) for ANY int input — including
    values far outside the nibble range (saturation) and odd widths."""
    rng = np.random.default_rng(seed)
    q = rng.integers(-300, 300, size=n).astype(np.int32)
    packed = np.asarray(quantize.pack_int4(jnp.asarray(q)))
    assert packed.dtype == np.uint8 and packed.shape[-1] == (n + 1) // 2
    out = np.asarray(quantize.unpack_int4(packed, n))
    np.testing.assert_array_equal(out, np.clip(q, -8, 7))


def _check_int4_nan(n: int, seed: int):
    """Float inputs round like the quantizer; NaN packs as 0 — mirroring
    fit_scale/fit_res_scale's NaN-exclusion so a padded/garbage row can
    never poison a nibble panel."""
    rng = np.random.default_rng(seed)
    z = (rng.standard_normal(n) * 6).astype(np.float32)
    nan_at = rng.integers(0, n, size=max(1, n // 4))
    z[nan_at] = np.nan
    out = np.asarray(quantize.unpack_int4(quantize.pack_int4(
        jnp.asarray(z)), n))
    expect = np.clip(np.round(np.where(np.isnan(z), 0.0, z)), -8, 7)
    np.testing.assert_array_equal(out, expect.astype(np.int8))
    assert (out[nan_at] == 0).all()


def _check_blob_roundtrip(g: int, k: int, cap: int, seed: int):
    """pack_coords_blob/unpack_coords_blob round-trip bit-exactly for any
    per-grain width mix, and the byte accounting is exact: 4-bit grains
    cost ceil(k*cap/2), 8-bit grains k*cap, full-width 2*k*cap."""
    rng = np.random.default_rng(seed)
    qm = rng.choice([quantize.INT4_QMAX, quantize.INT8_QMAX, 8191],
                    size=g).astype(np.int32)
    coords = np.stack([rng.integers(-q, q + 1, size=(k, cap))
                       for q in qm]).astype(np.int16)
    blob, offsets, widths = layout.pack_coords_blob(coords, qm)
    np.testing.assert_array_equal(
        widths, np.where(qm <= 7, 4, np.where(qm <= 127, 8, 16)))
    per = np.diff(offsets)
    expect = np.where(widths == 4, (k * cap + 1) // 2,
                      np.where(widths == 8, k * cap, 2 * k * cap))
    np.testing.assert_array_equal(per, expect)
    back = layout.unpack_coords_blob(blob, offsets, widths, k, cap)
    np.testing.assert_array_equal(back, coords)


def test_int4_roundtrip_seeded_sweep():
    for i, n in enumerate([1, 2, 3, 7, 8, 15, 16, 31, 33, 64, 65]):
        _check_int4_roundtrip(n, seed=100 + i)


def test_int4_nan_seeded_sweep():
    for i, n in enumerate([2, 3, 5, 9, 16, 31]):
        _check_int4_nan(n, seed=200 + i)


def test_blob_roundtrip_seeded_sweep():
    for i, (g, k, cap) in enumerate([(1, 1, 4), (2, 3, 8), (3, 5, 4),
                                     (4, 6, 16), (6, 8, 8), (5, 7, 16)]):
        _check_blob_roundtrip(g, k, cap, seed=300 + i)


def test_assign_grain_qmax_policy():
    qm = np.asarray(quantize.assign_grain_qmax(
        jnp.asarray([0.95, 0.95, 0.5, 0.99]), jnp.asarray([64, 4, 64, 8]),
        captured_min=0.85, min_rows=8))
    np.testing.assert_array_equal(
        qm, [quantize.INT4_QMAX, quantize.INT8_QMAX,
             quantize.INT8_QMAX, quantize.INT4_QMAX])


# ---------------------------------------------------------------------------
# recall by construction (in-process fused twin; sharded twin above)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bit_alloc", ["fixed", "density"])
def test_cascade_recall_by_construction_seeded(bit_alloc):
    """Through add/seal/delete/upsert/compact/maintain interleavings, the
    budgeted cascade with budgets=(pool, pool) >= every live slot returns
    exactly the brute-force L2 top-k over the live set — stage 1 cannot
    prune a real candidate when b1 covers the pool."""
    for ops, seed in [(("add", "seal", "delete", "upsert", "seal"), 5),
                      (("seal", "delete", "maintain", "add", "compact"), 9),
                      (("add", "add", "seal", "seal", "delete",
                        "maintain"), 17)]:
        mutation_property.mutation_interleaving_check(
            ops, seed, cold=False, scan_impl="cascade_ref", budgeted=True,
            bit_alloc=bit_alloc)


if HAVE_HYP:
    @settings(deadline=None, max_examples=50)
    @given(n=hst.integers(1, 65), seed=hst.integers(0, 2 ** 31))
    def test_int4_roundtrip_fuzz(n, seed):
        _check_int4_roundtrip(n, seed)

    @settings(deadline=None, max_examples=25)
    @given(n=hst.integers(2, 32), seed=hst.integers(0, 2 ** 31))
    def test_int4_nan_fuzz(n, seed):
        _check_int4_nan(n, seed)

    @settings(deadline=None, max_examples=25)
    @given(g=hst.integers(1, 6), k=hst.integers(1, 8),
           cap=hst.sampled_from([4, 8, 16]), seed=hst.integers(0, 2 ** 31))
    def test_blob_roundtrip_fuzz(g, k, cap, seed):
        _check_blob_roundtrip(g, k, cap, seed)

    @settings(deadline=None, max_examples=4)
    @given(ops=hst.lists(hst.sampled_from(mutation_property.OPS),
                         min_size=3, max_size=8),
           seed=hst.integers(0, 2 ** 20),
           bit_alloc=hst.sampled_from(["fixed", "density"]))
    def test_cascade_recall_by_construction_fuzz(ops, seed, bit_alloc):
        mutation_property.mutation_interleaving_check(
            ops, seed, cold=False, scan_impl="cascade_ref", budgeted=True,
            bit_alloc=bit_alloc)
else:
    def test_hypothesis_twins_skipped():
        pytest.skip("hypothesis not installed; fuzz twins of the seeded "
                    "sweeps above did not run")
