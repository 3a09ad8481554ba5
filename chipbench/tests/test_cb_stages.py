"""The readers of the store's own spans (``chipbench/stages.py``), on a
small recorded trace whose answers are worked out by hand."""
import pytest
from jax.profiler import ProfileData

from chipbench import stages, trace
from chipbench.registry import load_reader

# Device (chip 0), times in microseconds from 0 ns:
#   fusion.1 [10, 30) and [62, 90), both in jit_search_stacked
# Host thread "python", two calls:
#   chipbench.search [0, 50)   hntl.search [1, 49)
#     prepare [2, 6)  dispatch [6, 9)  readback [9, 35)  finalize [35, 47)
#   hntl.gc [50, 52) between the calls (generation 0)
#   chipbench.search [52, 100) hntl.search [53, 99)
#     prepare [54, 58), with hntl.gc [55, 57) inside it
#     dispatch [58, 60)  readback [60, 93)  finalize [93, 98)
# Another thread: an hntl.gc [0, 100) that is not the calling thread's.
_PS = 1_000_000  # picoseconds per microsecond
US = 1e-6
FUSION = "%fusion.1 = f32[1,20]{1,0} fusion(f32[1,40]{1,0} %a)"
NAMES = [trace.SPAN, stages.SEARCH, stages.PREPARE, stages.DISPATCH,
         stages.READBACK, stages.FINALIZE, stages.GC]


def _ev(meta, start_us, end_us):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_us * _PS} "
            f"duration_ps: {(end_us - start_us) * _PS} }}")


def _host(with_gc: bool) -> str:
    m = {n: i + 1 for i, n in enumerate(NAMES)}
    spans = [(trace.SPAN, 0, 50), (stages.SEARCH, 1, 49),
             (stages.PREPARE, 2, 6), (stages.DISPATCH, 6, 9),
             (stages.READBACK, 9, 35), (stages.FINALIZE, 35, 47),
             (trace.SPAN, 52, 100), (stages.SEARCH, 53, 99),
             (stages.PREPARE, 54, 58), (stages.DISPATCH, 58, 60),
             (stages.READBACK, 60, 93), (stages.FINALIZE, 93, 98)]
    if with_gc:
        spans += [(stages.GC, 50, 52), (stages.GC, 55, 57)]
    spans.sort(key=lambda s: (s[1], -s[2]))
    events = " ".join(_ev(m[n], a, b) for n, a, b in spans)
    metadata = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" }} }}' for n, i in m.items())
    return f'''
  lines {{ id: 7 name: "python" timestamp_ns: 0 {events} }}
  lines {{ id: 8 name: "other thread" timestamp_ns: 0
    {_ev(m[stages.GC], 0, 100)} }}
  {metadata}'''


def _xspace(with_gc: bool) -> str:
    return f'''
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {_ev(1, 10, 30)} {_ev(1, 62, 90)} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    {_ev(2, 10, 30)} {_ev(2, 62, 90)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "{FUSION}" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "jit_search_stacked(1)" }} }}
}}
planes {{
  id: 2 name: "/host:CPU" {_host(with_gc)}
}}
'''


def _view(tmp_path_factory, with_gc: bool):
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(_xspace(with_gc)))
    ops, spans, host = trace.load(str(d.parent.parent.parent))
    return trace.view(ops, spans, host, queries=2)


@pytest.fixture(scope="module")
def view(tmp_path_factory):
    return _view(tmp_path_factory, with_gc=True)


@pytest.fixture(scope="module")
def view_no_gc(tmp_path_factory):
    return _view(tmp_path_factory, with_gc=False)


def test_prepare_per_call(view):
    # (4 + 4) us over 2 calls; the collection inside call 2's prepare is
    # part of it
    got = load_reader("store.prepare_ms_per_call")(view)
    assert got == pytest.approx((4 + 4) / 2 * US * 1e3)


def test_dispatch_per_call(view):
    got = load_reader("store.dispatch_ms_per_call")(view)
    assert got == pytest.approx((3 + 2) / 2 * US * 1e3)


def test_readback_per_call_is_what_it_costs_beyond_the_device(view):
    # call 1: [9, 35) holds 20 us of device time [10, 30) -> 6 us
    # call 2: [60, 93) holds 28 us of device time [62, 90) -> 5 us
    got = load_reader("store.readback_ms_per_call")(view)
    assert got == pytest.approx((6 + 5) / 2 * US * 1e3)


def test_finalize_per_call(view):
    got = load_reader("store.finalize_ms_per_call")(view)
    assert got == pytest.approx((12 + 5) / 2 * US * 1e3)


def test_gc_per_call_counts_the_calling_thread_only(view, view_no_gc):
    # two 2 us collections on the calling thread, one between the calls
    # and one inside a stage; the other thread's is not seen
    got = load_reader("host.gc_ms_per_call")(view)
    assert got == pytest.approx((2 + 2) / 2 * US * 1e3)
    assert load_reader("host.gc_ms_per_call")(view_no_gc) == 0.0


@pytest.mark.parametrize("name", [
    "store.prepare_ms_per_call", "store.dispatch_ms_per_call",
    "store.readback_ms_per_call", "store.finalize_ms_per_call",
    "host.gc_ms_per_call"])
def test_readers_return_nothing_without_the_store_spans(name):
    """A program that writes no ``hntl.*`` span (an older commit) leaves
    each reader nothing to read: None, not 0."""
    bare = trace.View(ops=[], spans=[trace.Event(trace.SPAN, 0.0, 1.0)],
                      host=[trace.Event(trace.SPAN, 0.0, 1.0)],
                      lo=0.0, hi=1.0, queries=1)
    assert load_reader(name)(bare) is None
