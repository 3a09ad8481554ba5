"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracle.

Kernels run in interpret mode on CPU (the kernel body executes in python),
which validates the exact code that compiles for TPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.hntl_scan import hntl_scan, hntl_scan_single
from repro.kernels.ref import hntl_scan_ref, hntl_scan_single_ref


def _panel(rng, p, q, k, cap, qmag=500):
    return dict(
        zq=rng.integers(-qmag, qmag, (p, q, k)).astype(np.int32),
        rq=rng.random((p, q)).astype(np.float32),
        coords=rng.integers(-qmag, qmag, (p, k, cap)).astype(np.int16),
        res=rng.integers(0, 65535, (p, cap)).astype(np.int32),
        valid=rng.random((p, cap)) > 0.15,
        scale=(rng.random(p) * 0.01 + 1e-4).astype(np.float32),
        res_scale=(rng.random(p) * 1e-3 + 1e-5).astype(np.float32),
    )


SWEEP = [
    # (P, Q, k, cap) — covers tile-aligned, ragged, and tiny shapes
    (1, 1, 8, 128),
    (2, 3, 16, 256),
    (4, 128, 32, 512),
    (3, 130, 16, 384),       # non-multiples of both tile dims
    (2, 5, 64, 128),
    (1, 256, 8, 1024),
]


@pytest.mark.parametrize("p,q,k,cap", SWEEP)
def test_batched_scan_matches_oracle(rng, p, q, k, cap):
    a = _panel(rng, p, q, k, cap)
    out = hntl_scan(a["zq"], a["rq"], a["coords"], a["res"], a["valid"],
                    a["scale"], a["res_scale"], interpret=True)
    ref = hntl_scan_ref(a["zq"], a["rq"], a["coords"], a["res"], a["valid"],
                        a["scale"], a["res_scale"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("p,k,cap", [(1, 8, 128), (3, 16, 200), (8, 32, 512)])
def test_single_scan_matches_oracle(rng, p, k, cap):
    a = _panel(rng, p, 1, k, cap)
    out = hntl_scan_single(a["zq"][:, 0], a["rq"][:, 0], a["coords"],
                           a["res"], a["valid"], a["scale"], a["res_scale"],
                           interpret=True)
    ref = hntl_scan_single_ref(a["zq"][:, 0], a["rq"][:, 0], a["coords"],
                               a["res"], a["valid"], a["scale"],
                               a["res_scale"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_int32_exactness_at_extremes(rng):
    """Quantized coords at the int32-safe max must accumulate exactly."""
    from repro.core.index import int32_safe_qmax
    k = 32
    qmax = int32_safe_qmax(k)
    p, q, cap = 1, 2, 128
    zq = np.full((p, q, k), qmax, np.int32)
    coords = np.full((p, k, cap), -qmax, np.int16)
    a = _panel(rng, p, q, k, cap)
    out = hntl_scan(zq, a["rq"], coords, a["res"],
                    np.ones((p, cap), bool), a["scale"], a["res_scale"],
                    interpret=True)
    expected = (k * (2 * qmax) ** 2) * (a["scale"] ** 2)[0] \
        + a["res"][0].astype(np.float32) * a["res_scale"][0] + a["rq"][0][:, None]
    np.testing.assert_allclose(np.asarray(out[0]), expected, rtol=1e-6)
    assert k * (2 * qmax) ** 2 < 2 ** 31          # the invariant itself


def test_ops_sketch_and_mask_parity(rng):
    p, q, k, s, cap = 2, 4, 16, 8, 256
    a = _panel(rng, p, q, k, cap)
    sq = rng.integers(-100, 100, (p, q, s)).astype(np.int32)
    sketch = rng.integers(-100, 100, (p, s, cap)).astype(np.int8)
    sk_scale = (rng.random(p) * 0.01 + 1e-4).astype(np.float32)
    em = rng.random((p, cap)) > 0.3
    kw = dict(sq=sq, sketch=sketch, sketch_scale=sk_scale, extra_mask=em)
    r = ops.scan_batched(a["zq"], a["rq"], a["coords"], a["res"], a["valid"],
                         a["scale"], a["res_scale"], backend="ref", **kw)
    i = ops.scan_batched(a["zq"], a["rq"], a["coords"], a["res"], a["valid"],
                         a["scale"], a["res_scale"], backend="interpret", **kw)
    np.testing.assert_allclose(np.asarray(r), np.asarray(i),
                               rtol=1e-5, atol=1e-4)


def test_planner_scan_fn_via_vmap(rng):
    """The kernel must survive jax.vmap (the planner's calling convention)."""
    p, k, cap, Q = 3, 16, 128, 4
    a = _panel(rng, p, Q, k, cap)
    fn = ops.make_planner_scan_fn("interpret")
    out = jax.vmap(lambda z, r: fn(z, r, jnp.asarray(a["coords"]),
                                   jnp.asarray(a["res"]),
                                   jnp.asarray(a["valid"]),
                                   jnp.asarray(a["scale"]),
                                   jnp.asarray(a["res_scale"])))(
        jnp.asarray(a["zq"]).transpose(1, 0, 2), jnp.asarray(a["rq"]).T)
    ref = hntl_scan_ref(a["zq"], a["rq"], a["coords"], a["res"], a["valid"],
                        a["scale"], a["res_scale"])
    np.testing.assert_allclose(np.asarray(out).transpose(1, 0, 2),
                               np.asarray(ref), rtol=1e-5, atol=1e-4)


def test_ops_sketch_single_parity(rng):
    """scan_single with the sketch term: ref == interpret (pins the
    self-describing sketch invocation — zero residuals, unit residual
    scale — through the single-query path too)."""
    p, k, s, cap = 3, 16, 8, 256
    a = _panel(rng, p, 1, k, cap)
    kw = dict(sq=rng.integers(-100, 100, (p, s)).astype(np.int32),
              sketch=rng.integers(-100, 100, (p, s, cap)).astype(np.int8),
              sketch_scale=(rng.random(p) * 0.01 + 1e-4).astype(np.float32))
    r = ops.scan_single(a["zq"][:, 0], a["rq"][:, 0], a["coords"], a["res"],
                        a["valid"], a["scale"], a["res_scale"],
                        backend="ref", **kw)
    i = ops.scan_single(a["zq"][:, 0], a["rq"][:, 0], a["coords"], a["res"],
                        a["valid"], a["scale"], a["res_scale"],
                        backend="interpret", **kw)
    np.testing.assert_allclose(np.asarray(r), np.asarray(i),
                               rtol=1e-5, atol=1e-4)


def test_adaptive_query_block():
    """Q=1 serving path must not burn a 128-row MXU tile: the query block
    is the next multiple of 8 >= Q, capped at BLK_Q."""
    from repro.kernels.hntl_scan import BLK_Q, _query_block
    assert _query_block(1) == 8
    assert _query_block(8) == 8
    assert _query_block(9) == 16
    assert _query_block(128) == BLK_Q
    assert _query_block(1000) == BLK_Q


def test_adaptive_block_bit_for_bit(rng):
    """The adaptive tile height must not change results: the SAME query row
    scanned through the Q=1 path (8-row tile) and as part of a Q=128 batch
    (full 128-row tile) agrees BIT-FOR-BIT — and both match the ref oracle
    to float tolerance."""
    a = _panel(rng, 2, 128, 16, 256)
    full = hntl_scan(a["zq"], a["rq"], a["coords"], a["res"], a["valid"],
                     a["scale"], a["res_scale"], interpret=True)
    one = hntl_scan(a["zq"][:, :1], a["rq"][:, :1], a["coords"], a["res"],
                    a["valid"], a["scale"], a["res_scale"], interpret=True)
    assert np.array_equal(np.asarray(one), np.asarray(full)[:, :1])
    ref = hntl_scan_ref(a["zq"], a["rq"], a["coords"], a["res"], a["valid"],
                        a["scale"], a["res_scale"])
    np.testing.assert_allclose(np.asarray(full), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_invalid_slots_get_big(rng):
    a = _panel(rng, 2, 3, 8, 128)
    a["valid"][:] = False
    out = hntl_scan(a["zq"], a["rq"], a["coords"], a["res"], a["valid"],
                    a["scale"], a["res_scale"], interpret=True)
    assert (np.asarray(out) > 1e37).all()  # hntlint: ok H004 — BIG/2 bound


def test_invalid_slot_sentinel_is_single_sourced():
    """The 3.0e38 sentinel is hoisted to core.types.BIG; the kernels keep
    python-float copies (Pallas cannot capture traced constants) which must
    never drift — planner/store masks compare dists < BIG / 2 against what
    the kernels wrote."""
    from repro.core import scan as core_scan
    from repro.core.types import BIG
    from repro.kernels import fused_select as kfsel
    from repro.kernels import hntl_scan as kscan
    from repro.kernels import ref as kref
    from repro.models import hntl_attention as kv
    assert core_scan.NEG_BIG == BIG
    assert kscan.NEG_BIG == BIG
    assert kref.NEG_BIG == BIG
    assert ops.NEG_BIG == BIG
    assert kfsel.NEG_BIG == BIG
    assert kv.BIG == BIG


# ---------------------------------------------------------------------------
# Fused scan→select kernel: the select is exact, ties in lax.top_k order
# ---------------------------------------------------------------------------


def _select_args(seed, q=5, p=4, g=9, k=4, cap=200, s=0, ties=True):
    """Select-plane inputs; ``ties`` draws tiny integer ranges so many
    slots share a distance (the tie order is what is pinned)."""
    r = np.random.default_rng(seed)
    mag = 3 if ties else 1000
    gids = np.stack([r.choice(g, p, replace=False) for _ in range(q)])
    a = [gids.astype(np.int32),
         r.integers(-mag, mag + 1, (q, p, k)).astype(np.int32),
         (r.integers(0, 3, (q, p)) if ties else r.random((q, p)))
         .astype(np.float32),
         r.random((q, p)) > 0.15,
         r.integers(-mag, mag + 1, (g, k, cap)).astype(np.int16),
         r.integers(0, 3 if ties else 100, (g, cap)).astype(np.int32),
         r.random((g, cap)) > 0.2,
         r.permutation(g * cap).reshape(g, cap).astype(np.int32),
         (np.ones(g) if ties else r.random(g) + 0.5).astype(np.float32),
         np.ones(g, np.float32)]
    if s:
        a += [r.integers(-3, 4, (q, p, s)).astype(np.int32),
              r.integers(-3, 4, (g, s, cap)).astype(np.int8),
              np.ones(g, np.float32)]
    n_active = r.integers(1, p + 1, q).astype(np.int32)
    return [jnp.asarray(v) for v in a], jnp.asarray(n_active)


def _assert_select_equal(got, want):
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


# (ties, ragged, sketch, width, cap, budget): cap 200 pads to one
# two-column step a grain; the tilings add a one-column grain (cap 128), a
# three-column step (cap 300 -> 384 lanes), and a VMEM budget that fits one
# column only, so each grain of cap 300 takes three grid steps.
_TIE_CASES = [
    pytest.param(ties, ragged, sketch, width, 200, None,
                 id=f"{ties}-{ragged}-{sketch}-{width}")
    for ties in (True, False) for ragged in (False, True)
    for sketch in (0, 3) for width in (1, 7, 20, 130, 300)
] + [
    pytest.param(True, True, 3, width, cap, budget,
                 id=f"{name}-{width}")
    for name, cap, budget in (("grain128", 128, None),
                              ("cols3", 300, None),
                              ("split3", 300, 50_000))
    for width in (20, 130)
]


@pytest.mark.parametrize("ties,ragged,sketch,width,cap,budget", _TIE_CASES)
def test_fused_select_tie_order_matches_oracle(monkeypatch, ties, ragged,
                                               sketch, width, cap, budget):
    """The kernel's carried-set select keeps lax.top_k's order, earliest
    (probe, slot) first on equal distances, so the interpreted kernel and
    the jnp two-stage oracle ("fused_ref") agree bit for bit — widths below
    a lane column, at the pool, and over several columns; a grain scored in
    one grid step or, under a small VMEM budget, in several."""
    from repro.core.scan import blocksoa_select_ref
    from repro.kernels import fused_select as kfsel
    args, n_active = _select_args(width + sketch, cap=cap, s=sketch,
                                  ties=ties)
    kw = dict(n_active=n_active) if ragged else {}
    want = blocksoa_select_ref(*args, width=width, **kw)
    capp = -(-cap // 128) * 128
    select = kfsel.fused_scan_select
    if budget is not None:
        monkeypatch.setattr(kfsel, "TILE_VMEM_BYTES", budget)
        select = select.__wrapped__      # traced anew under the budget
    assert kfsel.select_tile(capp, 4, sketch) == (capp if budget is None
                                                  else 128)
    got = select(*args, width=width, interpret=True, **kw)
    _assert_select_equal(got, want)


@pytest.mark.parametrize("capp,k,s,want", [
    (128, 32, 8, 128), (2816, 32, 8, 2816), (2944, 32, 8, 2944),
    (128, 0, 0, 128), (384, 4, 3, 384), (2944, 0, 8, 2944),
    (1 << 16, 32, 8, 1 << 14), (1 << 17, 64, 0, 1 << 14)])
def test_select_tile_takes_the_widest_run_that_fits(capp, k, s, want):
    """A grid step's lane width is a multiple of 128 that divides the padded
    capacity and whose double-buffered blocks fit the VMEM budget: the whole
    grain at the benchmark's shapes (k=32, s=8, cohere cap 2,816, openai
    2,944), one column for a one-column cap, and the widest dividing run
    for caps too wide for one step."""
    from repro.kernels import fused_select as kfsel
    t = kfsel.select_tile(capp, k, s)
    assert t == want
    assert t % 128 == 0 and capp % t == 0
    per_lane = 2 * (2 * -(-k // 16) * 16 + -(-s // 32) * 32 + 96) + 8
    assert t * per_lane <= kfsel.TILE_VMEM_BYTES


def test_select_tile_splits_a_grain_under_a_small_budget(monkeypatch):
    """Blocks that overflow the budget split the grain into the widest
    dividing run of columns that fits; nothing fits -> one column."""
    from repro.kernels import fused_select as kfsel
    per_lane = 2 * (2 * 32 + 32 + 96) + 8             # k=32, s=8: 392 B
    monkeypatch.setattr(kfsel, "TILE_VMEM_BYTES", 11 * 128 * per_lane)
    assert kfsel.select_tile(2816, 32, 8) == 11 * 128  # 22 columns: 2 steps
    assert kfsel.select_tile(2944, 32, 8) == 128       # 23 columns: prime
    monkeypatch.setattr(kfsel, "TILE_VMEM_BYTES", 1)
    assert kfsel.select_tile(2816, 32, 8) == 128


def test_fused_select_without_coords_matches_zero_panel():
    """``coords=None`` (the cascade's stage 1) prices slots exactly like a
    scan of an all-zero coordinate panel."""
    from repro.core.scan import blocksoa_select_ref
    from repro.kernels.fused_select import fused_scan_select
    a, _ = _select_args(3, s=3)
    got = fused_scan_select(a[0], None, a[2], a[3], None, *a[5:], width=40,
                            interpret=True)
    zq0 = jnp.zeros(a[1].shape[:2] + (1,), jnp.int32)
    z0 = jnp.zeros((a[4].shape[0], 1, a[4].shape[2]), jnp.int16)
    want = blocksoa_select_ref(a[0], zq0, a[2], a[3], z0, *a[5:], width=40)
    _assert_select_equal(got, want)


def test_fused_select_splits_query_axis(monkeypatch):
    """A batch whose flat prefetch streams would overflow SMEM runs as
    several kernel calls along the query axis, with identical results."""
    from repro.core.scan import blocksoa_select_ref
    from repro.kernels import fused_select as kfsel
    a, n_active = _select_args(5, q=7, p=4)
    want = blocksoa_select_ref(*a, width=20, n_active=n_active)
    monkeypatch.setattr(kfsel, "PREFETCH_ENTRIES", 8)   # 2 queries a call
    got = kfsel.fused_scan_select.__wrapped__(*a, width=20, interpret=True,
                                              n_active=n_active)
    _assert_select_equal(got, want)


def test_kernel_backend_names_are_never_guessed():
    """An unknown backend name is an error, not the reference."""
    with pytest.raises(ValueError, match="unknown scan backend"):
        ops._resolve("palas")
    assert ops._resolve("interpret") == ("pallas", True)
    assert ops._resolve("pallas") == ("pallas", False)
