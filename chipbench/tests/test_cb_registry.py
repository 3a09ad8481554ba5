"""Cells find their configuration, mix, limits and metric readers by the
names BENCHMARK.json gives them; a new one is a new file and entry."""
import json
import shutil

import pytest

from chipbench import registry
from chipbench.system import search_kwargs
from chipbench.traffic import Generator


def test_every_cell_of_the_benchmark_loads():
    bench = registry.load_json(registry.ROOT / "BENCHMARK.json")
    for w in bench["workloads"]:
        cell = registry.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["batch"] >= 1
        assert cell.limits
        assert "setup_s" in {m.name for m in cell.end_to_end}
        assert cell.per_layer, f"{w['name']} reports no per-layer metric"
        for m, _ in cell.per_layer:
            assert m.moves in {e.name for e in cell.end_to_end}


def test_metrics_follow_their_workload_lists():
    batch = registry.load_cell("cohere768-1m.batch64")
    serial = registry.load_cell("cohere768-1m.serial")
    assert "qps" in {m.name for m in batch.end_to_end}
    assert "qps" not in {m.name for m in serial.end_to_end}
    assert {m.name for m, _ in serial.per_layer} == {"store.host_ms_per_call"}


def test_a_new_cell_is_files_and_an_entry(tmp_path):
    """A cell that names a new config, mix and metric is found with no
    code changed."""
    root = tmp_path / "checkout"
    shutil.copytree(registry.HERE, root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    here = root / "chipbench"
    (here / "configs" / "tiny.json").write_text(json.dumps({"name": "tiny"}))
    (here / "traffic" / "pairs.json").write_text(json.dumps(
        {"batch": 2, "rate_qps": 50, "search": {"ts_range": [0, 10]}}))
    (here / "limits" / "tiny.pairs.json").write_text('{"bad_rows": 0}')
    (here / "metrics" / "calls.per_s.py").write_text(
        "def read(view):\n    return view.calls / view.window_s\n")
    bench = {
        "configs": [{"name": "tiny", "file": "chipbench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.pairs", "config": "tiny",
                       "traffic": "pairs", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower",
                        "source": "host_clock"}],
        "per_layer": [{"name": "calls.per_s", "unit": "1/s",
                       "better": "higher", "source": "device_trace",
                       "layer": "x", "moves": "setup_s"}]}
    cell = registry.load_cell("tiny.pairs", bench=bench, root=root)
    assert cell.config == {"name": "tiny"}
    g = Generator(cell.traffic, 10, seed=1)
    assert g.open_loop and g.search == {"ts_range": [0, 10]}
    assert cell.limits == {"bad_rows": 0}
    (metric, read), = cell.per_layer
    assert metric.name == "calls.per_s"

    class V:
        calls, window_s = 6, 2.0
    assert read(V()) == 3.0
    with pytest.raises(KeyError):
        registry.load_cell("tiny.other", bench=bench, root=root)


def test_generator_cycles_every_query_in_a_seeded_order():
    mix = {"batch": 4}
    g = Generator(mix, 10, seed=2 ** 31 + 3)
    seen = [g.next() for _ in range(5)]
    assert all(len(b) == 4 for b in seen)
    flat = [int(i) for b in seen for i in b]
    assert sorted(flat[:10]) == list(range(10))
    assert flat[10:] == flat[:10]
    again = Generator(mix, 10, seed=2 ** 31 + 3)
    assert [int(i) for i in again.next()] == flat[:4]
    assert not again.open_loop and again.search == {}
    with pytest.raises(ValueError):
        Generator(dict(mix, loop="open"), 10, seed=1)
    with pytest.raises(ValueError):
        Generator(dict(mix, rate_qps=0), 10, seed=1)


def test_open_loop_arrivals_are_evenly_spaced_and_the_same_for_every_seed():
    mix = {"batch": 3, "rate_qps": 200.0}
    a, b = Generator(mix, 10, seed=1), Generator(mix, 10, seed=2 ** 40 + 5)
    assert [a.arrival(i) for i in range(4)] == pytest.approx(
        [0.0, 0.005, 0.010, 0.015])
    assert [a.arrival(i) for i in range(4)] == [b.arrival(i)
                                                  for i in range(4)]
    assert len(a.next(2)) == 2 and len(a.next()) == 3


def test_search_arguments_of_a_mix_are_passed_on_or_refused():
    assert search_kwargs({"ts_range": [1, 5], "nprobe": 8,
                          "adaptive": True}) == {
        "ts_range": (1, 5), "nprobe": 8, "adaptive": True}
    assert search_kwargs({}) == {}
    with pytest.raises(ValueError):
        search_kwargs({"tag_mask": 3})
