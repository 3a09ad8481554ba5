"""Interpret-mode end-to-end parity suite for the ScanPlane registry.

Every registered backend must produce results identical (ids bit-for-bit,
dists to float tolerance) to the "ref" plane through the REAL data planes —
``search_stacked`` via ``VectorStore.search`` and the forced-4-device
``search_stacked_sharded`` — across warm/cold tiers, sketch on/off, and the
in-situ predicates (tag filter, ts filter, tombstone liveness).

The select planes ("fused", "fused_ref") additionally have a *structural*
contract: they emit [Q, width] and never materialize the per-query probed
panel gather — pinned here by poisoning ``planner._gather_probed_panels``.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HNTLConfig, build, scan_plane_names
from repro.core import index as index_mod
from repro.core import planner, scanplane
from repro.core.store import VectorStore

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
D, SEG_ROWS, N_SEG = 24, 128, 2

# "pallas"/"fused" (compiled) need real TPU hardware; on CPU the Pallas
# kernel bodies run in interpreter mode, asked for by name ("interpret",
# "fused_interpret" — test ids drop the suffix, see conftest.py).
BACKENDS = ["interpret", "fused_interpret", "fused_ref", "auto"]
SELECT_BACKENDS = ["fused_interpret", "fused_ref"]


def _cfg(s: int) -> HNTLConfig:
    return HNTLConfig(d=D, k=6, s=s, n_grains=4, nprobe=4, pool=32, block=32)


def _build_store(cold: bool, s: int):
    rng = np.random.default_rng(5)
    st = VectorStore(_cfg(s), seal_threshold=SEG_ROWS, cold_tier=cold)
    x = rng.standard_normal((N_SEG * SEG_ROWS, D)).astype(np.float32)
    for i in range(N_SEG):
        st.add(x[i * SEG_ROWS:(i + 1) * SEG_ROWS],
               tags=[1 << i] * SEG_ROWS, ts=[float(i)] * SEG_ROWS)
    assert st.n_segments == N_SEG and not st._mem
    q = (x[:4] + 0.01 * rng.standard_normal((4, D))).astype(np.float32)
    return st, x, q


@pytest.fixture(scope="module",
                params=["warm", "warm_sketch", "cold"])
def store(request):
    cold = request.param == "cold"
    s = 4 if request.param == "warm_sketch" else 0
    return _build_store(cold, s)


CASES = [dict(), dict(tag_mask=2), dict(ts_range=(0.0, 1.0)),
         dict(tag_mask=1, ts_range=(0.0, 2.0))]


def _assert_same(res, ref):
    assert np.array_equal(np.asarray(res.ids, np.int64),
                          np.asarray(ref.ids, np.int64))
    np.testing.assert_allclose(np.asarray(res.dists), np.asarray(ref.dists),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_stacked_parity_all_predicates(store, backend):
    """Fused stacked plane: every backend == "ref" for every predicate."""
    st, x, q = store
    for case in CASES:
        ref = st.search(q, topk=5, mode="B", scan_impl="ref", **case)
        res = st.search(q, topk=5, mode="B", scan_impl=backend, **case)
        _assert_same(res, ref)


@pytest.mark.parametrize("backend", BACKENDS)
def test_stacked_parity_mode_a_and_single_query(store, backend):
    st, x, q = store
    ref = st.search(q, topk=5, mode="A", scan_impl="ref")
    res = st.search(q, topk=5, mode="A", scan_impl=backend)
    _assert_same(res, ref)
    # the Q=1 serving shape
    ref1 = st.search(q[:1], topk=3, mode="B", scan_impl="ref")
    res1 = st.search(q[:1], topk=3, mode="B", scan_impl=backend)
    _assert_same(res1, ref1)


@pytest.mark.parametrize("backend", BACKENDS)
def test_stacked_parity_under_liveness(store, backend):
    """Tombstones ride the in-situ mask identically through every backend,
    and deleted rows never resurface."""
    st, x, q = store
    child = st.branch()                      # keep the module store pristine
    victims = np.asarray(np.argsort(
        ((x - q[:1]) ** 2).sum(1))[:3])      # the 3 closest rows to q[0]
    child.delete(victims)
    ref = child.search(q, topk=5, mode="B", scan_impl="ref")
    res = child.search(q, topk=5, mode="B", scan_impl=backend)
    _assert_same(res, ref)
    assert not np.isin(victims, np.asarray(res.ids)).any()


@pytest.mark.parametrize("backend", SELECT_BACKENDS)
def test_per_segment_route_mode_parity(store, backend):
    st, x, q = store
    ref = st.search(q, topk=5, mode="B", route_mode="per_segment",
                    scan_impl="ref")
    res = st.search(q, topk=5, mode="B", route_mode="per_segment",
                    scan_impl=backend)
    _assert_same(res, ref)


def test_sharded_parity_forced_4_devices(store):
    """search_stacked_sharded under every backend on a forced-4-device CPU
    mesh: identical to the sharded "ref" plane (same per-shard knobs),
    warm and cold, masked and unmasked, with tombstones."""
    if store[0].cold_tier and store[0].cfg.s:
        pytest.skip("combination not built")
    cold = store[0].cold_tier
    s = store[0].cfg.s
    out = _run_sub(f"""
        import numpy as np
        from test_scan_plane import _build_store, _assert_same, BACKENDS
        from repro.launch.mesh import make_search_mesh
        st, x, q = _build_store({cold!r}, {s!r})
        st.delete(np.arange(5))
        mesh = make_search_mesh(4)
        for case in (dict(), dict(tag_mask=2), dict(ts_range=(0.0, 1.0))):
            ref = st.search(q, topk=5, mode="B", scan_impl="ref", mesh=mesh,
                            **case)
            for backend in BACKENDS:
                res = st.search(q, topk=5, mode="B", scan_impl=backend,
                                mesh=mesh, **case)
                _assert_same(res, ref)
        print("OK")
    """)
    assert "OK" in out


def _run_sub(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC + os.pathsep + os.path.dirname(__file__)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    return out.stdout


# ---------------------------------------------------------------------------
# Select-plane structural contract: O(Q·pool) candidate state, no gather
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", SELECT_BACKENDS)
def test_select_plane_emits_pool_width(store, backend):
    """The candidate stage of a select plane is [Q, width] — the [Q, P*cap]
    slot matrix never exists."""
    st, x, q = store
    stacked = st._stacked_for(st._segments)["plane"]
    gids, _ = planner.routing.route(stacked.index.routing,
                                    jnp.asarray(q), 4)
    d, r = planner.candidate_stage(
        stacked.index, jnp.asarray(q), gids, envelope_frac=0.25,
        qeff=1000, width=16, scan_impl=backend)
    assert d.shape == (q.shape[0], 16) and r.shape == (q.shape[0], 16)
    # ascending pool, pruned tail = (BIG-ish, -1)
    dv = np.asarray(d)
    assert (np.diff(dv, axis=1) >= 0).all()


_FRESH_POOL = iter(range(41, 200, 2))    # unique pool statics => fresh traces


@pytest.mark.parametrize("backend", SELECT_BACKENDS)
def test_select_plane_never_gathers_probed_panels(store, backend,
                                                  monkeypatch):
    """Poison the probed-panel gather: select backends must never reach it
    (that materialization is exactly what they exist to eliminate), gather
    backends must (sanity that the poison works).  Unique pool values force
    fresh traces past the jit cache — the gather happens at trace time."""
    st, x, q = store

    def poisoned(g, gids):
        raise AssertionError("select plane materialized coords[gids]")

    monkeypatch.setattr(planner, "_gather_probed_panels", poisoned)
    st.search(q, topk=7, mode="B", pool=next(_FRESH_POOL),
              scan_impl=backend)                           # must not raise
    with pytest.raises(Exception, match="materialized"):
        st.search(q, topk=7, mode="B", pool=next(_FRESH_POOL),
                  scan_impl="ref")


# ---------------------------------------------------------------------------
# Registry + candidate-validity threshold (satellites)
# ---------------------------------------------------------------------------


def test_plane_cache_shared_across_backend_aliases():
    """The plane cache keys on the RESOLVED backend name: None/"auto" and
    the backend they resolve to share one cached device plane (no duplicate
    stack, no re-stack on alias switch); a genuinely different backend gets
    its own slot."""
    st, x, q = _build_store(False, 0)
    st.stack_cache_entries = 4
    st.search(q, topk=3, scan_impl=None)
    st.search(q, topk=3, scan_impl="auto")
    resolved = scanplane.get_scan_plane(None).name
    st.search(q, topk=3, scan_impl=resolved)
    assert len(st._stack_cache) == 1
    other = "fused_ref" if resolved != "fused_ref" else "ref"
    st.search(q, topk=3, scan_impl=other)
    assert len(st._stack_cache) == 2


def test_registry_names_and_errors():
    names = scan_plane_names()
    for n in ("ref", "pallas", "interpret", "fused", "fused_interpret",
              "fused_ref", "auto"):
        assert n in names
    with pytest.raises(ValueError, match="unknown scan plane"):
        scanplane.get_scan_plane("nope")
    # CPU auto == ref; explicit kinds
    assert scanplane.get_scan_plane(None).name in ("ref", "fused")
    assert scanplane.get_scan_plane("fused").kind == scanplane.SELECT
    assert scanplane.get_scan_plane("ref").kind == scanplane.GATHER


@pytest.mark.parametrize("backend", ["fused", "cascade", "pallas"])
def test_compiled_kernel_planes_never_fall_back_on_cpu(backend):
    """A compiled-kernel plane on a host without a TPU fails loudly: it is
    never quietly interpreted or swapped for a reference plane (the
    interpreter is asked for by name, e.g. "fused_interpret")."""
    if jax.default_backend() == "tpu":
        pytest.skip("the compiled kernels run here")
    st, x, q = _build_store(False, 0)
    with pytest.raises(ValueError, match="interpret"):
        st.search(q, topk=3, scan_impl=backend)


@pytest.mark.parametrize("mode", ["A", "B"])
@pytest.mark.parametrize("backend", ["ref", "fused_ref", "fused_interpret"])
def test_fully_pruned_pool_returns_all_minus_one(mode, backend):
    """Candidate-validity threshold regression (BIG/2 everywhere): a pool
    with every slot pruned by the in-situ predicate must come back as all
    id -1 through BOTH the legacy planner.search path and the stacked
    ``_candidate_epilogue`` path — never as real-looking ids."""
    rng = np.random.default_rng(1)
    x = rng.standard_normal((96, D)).astype(np.float32)
    cfg = _cfg(0)
    idx, _ = build(x, cfg)
    em = jnp.zeros((idx.grains.n_grains, idx.grains.cap), bool)
    res = index_mod.search(idx, x[:3], cfg, topk=4, mode=mode,
                           scan_impl=backend, extra_mask=em)
    assert (np.asarray(res.ids) == -1).all()
    assert (np.asarray(res.dists) >= planner.BIG / 2).all()
    # stacked epilogue path: a predicate no record matches
    st = VectorStore(cfg, seal_threshold=96)
    st.add(x, tags=[1] * 96)
    res2 = st.search(x[:3], topk=4, mode=mode, tag_mask=8,
                     scan_impl=backend)
    assert (np.asarray(res2.ids) == -1).all()
