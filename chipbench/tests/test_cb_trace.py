"""The reduction from a profiler trace to per-layer metrics, on a small
recorded trace whose answers are worked out by hand."""
import pytest
from jax.profiler import ProfileData

from chipbench import trace
from chipbench.registry import load_reader

# Device (chip 0, "XLA Ops"; programs on "XLA Modules"), times in
# microseconds from 1000 ns:
#   fused_scan_select.1  [1, 41)    in jit_search_stacked [1, 46)
#   fusion.3             [41, 46)   in jit_search_stacked
#   fused_scan_select.1  [61, 101)  in jit_search_stacked [61, 104)
#   fusion.3             [99, 104)  in jit_search_stacked (overlaps)
#   copy.1               [120, 122) in jit_other [120, 122)
# Host thread "python": two call spans [0, 50) and [55, 125), inside each
# a dispatch event, and between them the merge that ran on the host.
_PS = 1_000_000  # picoseconds per microsecond
KERNEL = ("%fused_scan_select.1 = (f32[4,1,128]{2,1,0:T(1,128)}) "
          "custom-call(s32[48]{0} %reshape.0)")
FUSION = "%fusion.3 = f32[4,10]{1,0:T(8,128)} fusion(f32[4,20]{1,0} %a)"
COPY = "%copy.1 = f32[4]{0} copy(f32[4]{0} %b)"


def _ev(meta, start_us, dur_us):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_us * _PS} "
            f"duration_ps: {dur_us * _PS} }}")


XSPACE = f'''
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000
    {_ev(1, 0, 40)} {_ev(2, 40, 5)} {_ev(1, 60, 40)} {_ev(2, 98, 5)}
    {_ev(3, 119, 2)}
  }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 1000
    {_ev(4, 0, 45)} {_ev(4, 60, 43)} {_ev(5, 119, 2)}
  }}
  lines {{ id: 3 name: "Async XLA Ops" timestamp_ns: 1000
    {_ev(3, 0, 200)}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "{KERNEL}" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "{FUSION}" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "{COPY}" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "jit_search_stacked(12)" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "jit_other(34)" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 7 name: "python" timestamp_ns: 0
    {_ev(1, 0, 50)} {_ev(2, 0, 1)} {_ev(3, 50, 5)} {_ev(1, 55, 70)}
    {_ev(2, 55, 6)}
  }}
  lines {{ id: 8 name: "other thread" timestamp_ns: 0
    {_ev(4, 0, 200)}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "{trace.SPAN}" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "dispatch" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "merge" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "background" }} }}
}}
'''
US = 1e-6


@pytest.fixture(scope="module")
def view(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    ops, spans, host = trace.load(str(d.parent.parent.parent))
    return trace.view(ops, spans, host, queries=8,
                      work={"ops": 197e12 * 8e-6, "bytes": 819e9 * 2e-6},
                      peaks={"bf16_flops": 197e12,
                             "hbm_bytes_per_s": 819e9})


def test_load_keeps_chip0_ops_call_spans_and_calling_thread(view):
    assert [e.name for e in view.ops] == [
        "fused_scan_select.1", "fusion.3", "fused_scan_select.1",
        "fusion.3", "copy.1"]
    assert [e.module for e in view.ops] == ["jit_search_stacked"] * 4 + [
        "jit_other"]
    assert view.ops[1].label == "fusion.3 = f32[4,10]"
    assert view.calls == 2 and view.queries == 8
    assert {e.name for e in view.host} == {trace.SPAN, "dispatch", "merge"}
    assert view.lo == pytest.approx(0.0)
    assert view.hi == pytest.approx(125 * US)


def test_union_of_busy_intervals(view):
    # [1, 46) + [61, 104) + [120, 122): overlapping ops counted once
    assert view.busy_s() == pytest.approx((45 + 43 + 2) * US)
    assert view.busy_s(55 * US, 125 * US) == pytest.approx((43 + 2) * US)
    assert trace.union([(0, 2), (1, 3), (5, 6), (6, 7)], 0.5, 6.5) == \
        [(0.5, 3), (5, 6.5)]


def test_top_ops_sum_each_op_by_name(view):
    top = view.top_ops(10)
    assert top[0] == [
        "fused_scan_select.1 = (f32[4,1,128]", pytest.approx(80 * US)]
    assert top[1] == ["fusion.3 = f32[4,10]", pytest.approx(10 * US)]


def test_gaps_between_busy_intervals():
    assert trace.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert trace.gaps([], 0, 1) == [(0, 1)]


def test_kernel_time_and_other_search_ops(view):
    kernel = load_reader("fused_select.us_per_query")(view)
    assert kernel == pytest.approx(80 * US / 8 * 1e6)
    planner = load_reader("planner.device_us_per_query")(view)
    assert planner == pytest.approx(10 * US / 8 * 1e6)


def test_roofline_share_of_kernel_time(view):
    # least time = max(8 us of operations, 2 us of bytes) over 80 us
    share = load_reader("fused_select_roofline")(view)
    assert share == pytest.approx(8 / 80 * 100)


def test_idle_share_and_host_time_per_call(view):
    idle = load_reader("device.idle_share")(view)
    assert idle == pytest.approx(1 - 90 / 125)
    host = load_reader("store.host_ms_per_call")(view)
    # call 1: 50 - 45 us busy; call 2: 70 - 45 us busy
    assert host == pytest.approx(((50 - 45) + (70 - 45)) / 2 * 1e-3)


def test_gap_attribution_names_what_the_calling_thread_did(view):
    gaps = view.idle_gaps(10)
    # [104, 120) inside call 2, [46, 61) between the calls (the merge ran
    # there), [122, 125) inside call 2, [0, 1) in call 1's dispatch; never
    # what another thread did
    assert [g[0] for g in gaps] == [trace.SPAN, "merge", trace.SPAN,
                                    "dispatch"]
    assert [g[1] for g in gaps] == pytest.approx([16 * US, 15 * US,
                                                  3 * US, 1 * US])
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)


def test_readers_return_nothing_without_events():
    empty = trace.View(ops=[], spans=[], host=[], lo=0.0, hi=1.0, queries=0)
    for name in ("fused_select.us_per_query", "fused_select_roofline",
                 "planner.device_us_per_query", "device.idle_share",
                 "store.host_ms_per_call"):
        assert load_reader(name)(empty) is None
