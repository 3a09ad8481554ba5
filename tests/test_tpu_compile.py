"""Compile the search path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers a kernel at the main path's real widths
(k=32, s=8, cap=1024 over G=1024 grains) for one chip of a described
``v5e:2x2`` topology and asks the TPU compiler for the program, so what
Mosaic or XLA:TPU would refuse on the chip (an unlowerable primitive, an
int32 matmul, SMEM or VMEM overflow) fails here, with no chip.  Each
compiled program must hold the kernel as a ``tpu_custom_call`` — compiled,
not interpreted.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.cascade import make_cascade_runner
from repro.kernels.fused_select import PREFETCH_ENTRIES, fused_scan_select
from repro.kernels.hntl_scan import hntl_scan, hntl_scan_single

K, S, CAP, G = 32, 8, 1024, 1024
Q, P, WIDTH = 64, 16, 20
# The largest batch one kernel call takes (its flat prefetch streams fill
# half of SMEM), and a batch twice that, which is split along queries.
SMEM_BATCHES = [(PREFETCH_ENTRIES // P, P), (1024, 128)]
# The benchmark's calls (q, p, cap, G): cohere768-1m and openai1536-500k
# 64-query batches, and the cohere serial call; one grid step a grain.
BENCH_CALLS = [(64, 192, 2816, 512), (64, 96, 2944, 256), (1, 192, 2816, 512)]
# A cap whose blocks overflow the VMEM budget: two grid steps a grain.
SPLIT_CALL = (8, 4, 32768, 8)
SELECT_CALLS = ([pytest.param(q, p, CAP, G, id=f"{q}x{p}")
                 for q, p in SMEM_BATCHES]
                + [pytest.param(q, p, cap, g, id=f"{q}x{p}-cap{cap}")
                   for q, p, cap, g in BENCH_CALLS + [SPLIT_CALL]])


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip cannot be read back from the
        # persistent cache without that chip
        cache = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no target
            jax.config.update("jax_enable_compilation_cache", cache)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", cache)


def _compile(fn, args, sharding):
    shaped = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
              for a in args]
    text = jax.jit(fn).lower(*shaped).compile().as_text()
    assert "tpu_custom_call" in text, "kernel was not compiled for the TPU"
    return text


def _select_shapes(q, p, *, sketch=False, cap=CAP, g=G):
    sd = jax.ShapeDtypeStruct
    a = [sd((q, p), jnp.int32), sd((q, p, K), jnp.int32),
         sd((q, p), jnp.float32), sd((q, p), jnp.bool_),
         sd((g, K, cap), jnp.int16),
         sd((g, cap), jnp.int32), sd((g, cap), jnp.bool_),
         sd((g, cap), jnp.int32), sd((g,), jnp.float32),
         sd((g,), jnp.float32)]
    if sketch:
        a += [sd((q, p, S), jnp.int32), sd((g, S, cap), jnp.int8),
              sd((g,), jnp.float32)]
    return a


@pytest.mark.parametrize("variant", ["plain", "sketch", "n_active",
                                     "tenant"])
def test_fused_scan_select_compiles(one_chip, variant):
    args = _select_shapes(Q, P, sketch=variant == "sketch")
    n_tenants = 3
    if variant == "n_active":
        args.append(jax.ShapeDtypeStruct((Q,), jnp.int32))

        def fn(*a):
            return fused_scan_select(*a[:-1], width=WIDTH, n_active=a[-1])
    elif variant == "tenant":
        args += [jax.ShapeDtypeStruct((n_tenants, G, CAP), jnp.bool_),
                 jax.ShapeDtypeStruct((Q,), jnp.int32)]

        def fn(*a):
            return fused_scan_select(*a[:-2], width=WIDTH,
                                     tenant_mask=a[-2], tenant_ix=a[-1])
    else:
        def fn(*a):
            return fused_scan_select(*a, width=WIDTH)
    _compile(fn, args, one_chip)


@pytest.mark.parametrize("q,p,cap,g", SELECT_CALLS)
def test_fused_scan_select_compiles_at_smem_bound(one_chip, q, p, cap, g):
    """The scalar-prefetch streams stay inside v5e's 1 MiB SMEM: one call
    at the bound, and a batch past it split into several calls.  The
    benchmark's calls, each grain's whole capacity one grid step (its
    blocks in VMEM), and a grain too wide for one step, compile to one
    kernel call each."""
    text = _compile(lambda *a: fused_scan_select(*a, width=WIDTH),
                    _select_shapes(q, p, sketch=True, cap=cap, g=g),
                    one_chip)
    calls = -(-q * p // PREFETCH_ENTRIES)
    assert text.count("custom_call_target=\"tpu_custom_call\"") == calls


@pytest.mark.parametrize("budgets", [None, (256, 32)],
                         ids=["lossless", "budgeted"])
def test_cascade_stage1_compiles(one_chip, budgets):
    """The cascade's stage 1 runs the fused kernel without the coordinate
    term; the whole staged runner compiles around it."""
    runner = make_cascade_runner("kernel")
    _compile(lambda *a: runner(*a, width=WIDTH, budgets=budgets),
             _select_shapes(Q, P, sketch=True), one_chip)


def _scan_shapes(p, q, batched):
    sd = jax.ShapeDtypeStruct
    zq = sd((p, q, K), jnp.int32) if batched else sd((p, K), jnp.int32)
    rq = sd((p, q), jnp.float32) if batched else sd((p,), jnp.float32)
    return [zq, rq, sd((p, K, CAP), jnp.int16), sd((p, CAP), jnp.int32),
            sd((p, CAP), jnp.bool_), sd((p,), jnp.float32),
            sd((p,), jnp.float32)]


def test_hntl_scan_single_compiles(one_chip):
    _compile(hntl_scan_single, _scan_shapes(P, 1, False), one_chip)


@pytest.mark.parametrize("q", [Q, PREFETCH_ENTRIES // P])
def test_hntl_scan_batched_compiles(one_chip, q):
    """Mosaic has no int32 matmul: the exact cross term runs as bf16 limb
    products on the MXU."""
    _compile(hntl_scan, _scan_shapes(P, q, True), one_chip)



def test_search_program_names_the_fused_kernel(one_chip):
    """The whole search program, compiled for the chip: the scan→select
    kernel is the HLO instruction ``fused_scan_select`` (what the
    benchmark's trace readers match), beside the program's named scopes."""
    from repro.core import HNTLConfig, planner, spans
    from repro.core.store import VectorStore
    cfg = HNTLConfig(d=K, k=8, s=S, n_grains=4, nprobe=4, pool=WIDTH)
    st = VectorStore(cfg, seal_threshold=512)
    st.add(np.random.default_rng(0).standard_normal(
        (1024, K)).astype(np.float32))
    plane = st._stacked_for(st.snapshot().segments)["plane"]
    shaped = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (plane, jnp.zeros((1, K), jnp.float32)))
    text = planner.search_stacked.lower(
        *shaped, nprobe=4, pool=WIDTH, topk=10,
        scan_impl="fused").compile().as_text()
    assert re.search(r"%fused_scan_select(\.\d+)? = .*custom-call", text)
    for scope in (spans.ROUTE, spans.PROJECT, spans.RERANK):
        assert f"/{scope}/" in text, scope
