"""The store's trace spans (``core/spans.py``), read back from a profile.

Each test records ``jax.profiler.trace`` around searches of a tiny store
and reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``, as an
operator's xprof or the benchmark's trace readers would: every
``hntl.search`` holds its stages in order, on the calling thread, one
dispatch/readback pair per program it runs; re-stacks, liveness uploads
and collections of Python's collector get spans of their own.
"""
import gc
import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import HNTLConfig, spans
from repro.core.store import VectorStore

D, SEG, N_SEG, Q = 16, 64, 3, 4
LETTER = {spans.PREPARE: "P", spans.DISPATCH: "D", spans.READBACK: "R",
          spans.COLD_RERANK: "C", spans.FINALIZE: "F"}


def _cfg():
    return HNTLConfig(d=D, k=4, s=0, n_grains=4, nprobe=4, pool=32, block=8,
                      hub_size=2)


def _store(tmp_path, *, segments=N_SEG, **kw):
    x = np.random.default_rng(3).standard_normal(
        (N_SEG * SEG, D)).astype(np.float32)
    st = VectorStore(_cfg(), seal_threshold=SEG, cold_dir=str(tmp_path),
                     **kw)
    for i in range(segments):
        st.add(x[i * SEG:(i + 1) * SEG])
    return st, x[:Q] + 0.01


def _traced(tmp_path, fn):
    """The hntl.* host events ``fn`` leaves in a profile, as (line, name,
    start_ns, end_ns, args) in start order, plus what ``fn`` returned."""
    out = str(tmp_path / "trace")
    with jax.profiler.trace(out):
        got = fn()
    (path,) = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                        recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("hntl."):
                    events.append((li, ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns,
                                   dict(ev.stats)))
    return sorted(events, key=lambda e: (e[2], -e[3])), got


def _calls(events):
    """Each hntl.search span with the hntl.search.* stages inside it."""
    out = []
    for call in (e for e in events if e[1] == spans.SEARCH):
        inside = [e for e in events if e[1].startswith(spans.SEARCH + ".")
                  and e[2] >= call[2] and e[3] <= call[3]]
        out.append((call, inside))
    return out


def _stage_string(stages) -> str:
    return "".join(LETTER[e[1]] for e in stages)


PATHS = {
    # name: (store kwargs, search kwargs, stage pattern)
    "fused": ({}, {}, "PDRF"),
    "sharded": ({}, {"mesh": 1}, "PDRF"),
    "cold": ({"cold_tier": True}, {}, "PDRCF"),
    "tiered": ({"device_budget": 0, "prefetch_grains": 2}, {},
               "P(DR)+F"),
    "adaptive": ({}, {"adaptive": True, "probe_margin": 1e6},
                 "PDR(DR)+F"),
    "looped": ({}, {"fused": False}, "P(DR)+F"),
}


@pytest.mark.parametrize("path", list(PATHS))
def test_each_search_holds_its_stages_in_order(tmp_path, path):
    store_kw, search_kw, pattern = PATHS[path]
    st, q = _store(tmp_path, **store_kw)
    if search_kw.get("mesh"):
        from repro.launch.mesh import make_search_mesh
        search_kw = dict(search_kw, mesh=make_search_mesh(1))
    st.search(q, **search_kw)                  # compile outside the trace
    events, _ = _traced(tmp_path, lambda: [st.search(q, **search_kw),
                                           st.search(q[:1], **search_kw)])
    calls = _calls(events)
    assert [c[0][4] for c in calls] == [{"queries": Q, "call": 1},
                                        {"queries": 1, "call": 2}]
    for call, stages in calls:
        assert re.fullmatch(pattern, _stage_string(stages)), \
            _stage_string(stages)
        # on the calling thread, one at a time, in order
        assert {e[0] for e in stages} == {call[0]}
        for a, b in zip(stages, stages[1:]):
            assert a[3] <= b[2]


def test_memtable_only_store_prepares_and_finalizes(tmp_path):
    st, q = _store(tmp_path, segments=0)
    st.add(np.ones((5, D), np.float32))
    assert st.n_segments == 0
    events, res = _traced(tmp_path, lambda: st.search(q))
    ((_, stages),) = _calls(events)
    assert _stage_string(stages) == "PF"
    assert np.asarray(res.ids).shape == (Q, 10)


def test_plane_stack_once_after_seal_and_not_on_a_repeat(tmp_path):
    st, q = _store(tmp_path)
    st.search(q)
    x = np.random.default_rng(5).standard_normal((SEG, D)).astype(np.float32)

    def seal_then_search_twice():
        st.add(x)                               # seals a fourth segment
        st.search(q)
        st.search(q)

    events, _ = _traced(tmp_path, seal_then_search_twice)
    stacks = [e for e in events if e[1] == spans.PLANE_STACK]
    assert st.n_segments == N_SEG + 1 and len(stacks) == 1
    (first, first_stages), (_, second_stages) = _calls(events)
    prepare = first_stages[0]
    assert prepare[1] == spans.PREPARE
    assert prepare[2] <= stacks[0][2] and stacks[0][3] <= prepare[3]
    assert not [e for e in events if e[1] == spans.PLANE_STACK
                and e[2] >= second_stages[0][2]]


def test_plane_live_once_after_a_delete(tmp_path):
    st, q = _store(tmp_path)
    st.search(q)

    def delete_then_search_twice():
        st.delete([0, 1, 2])
        st.search(q)
        st.search(q)

    events, _ = _traced(tmp_path, delete_then_search_twice)
    assert [e[1] for e in events].count(spans.PLANE_LIVE) == 1


def test_forced_collection_leaves_a_gc_span(tmp_path):
    _store(tmp_path, segments=0)               # installs the hook
    events, _ = _traced(tmp_path, lambda: gc.collect())
    assert {"generation": 2} in [e[4] for e in events if e[1] == spans.GC]


def test_gc_hook_installs_once_and_can_be_removed(tmp_path):
    try:
        _store(tmp_path, segments=0)
        _store(tmp_path, segments=0)
        assert gc.callbacks.count(spans._on_gc) == 1
        spans.remove_gc_hook()
        assert spans._on_gc not in gc.callbacks
        events, _ = _traced(tmp_path, lambda: gc.collect())
        assert not [e for e in events if e[1] == spans.GC]
    finally:
        spans.install_gc_hook()


def test_search_spans_record_nothing_with_no_trace_active():
    with spans.SearchSpans(queries=1, call=0) as call_spans:
        call_spans.stage(spans.DISPATCH)
        assert call_spans._call is None and call_spans._stage is None
    spans.UNTRACED.stage(spans.READBACK)
    assert spans.UNTRACED._stage is None


def test_search_program_names_its_device_scopes(tmp_path):
    """Routing, projection and the epilogue carry their ``named_scope``
    in the search program's op metadata."""
    from repro.core import planner
    st, q = _store(tmp_path)
    entry = st._stacked_for(st.snapshot().segments)
    text = planner.search_stacked.lower(
        entry["plane"], q, nprobe=4, pool=32, topk=10,
        scan_impl="fused_ref").as_text(debug_info=True)
    for scope in (spans.ROUTE, spans.PROJECT, spans.RERANK):
        assert scope + "/" in text, scope
