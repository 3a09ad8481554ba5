"""``correct`` at a size a test run holds: a sound run passes, and the
control and each fault a cell can have fail.

These runs skip the look for a chip and drive the rest of a run on the
CPU, where the store's default scan plane is its jnp reference.  The
limits are the cells' own (``chipbench/limits/``).
"""
import time

import numpy as np
import pytest

from chipbench import registry
from chipbench.bench import run_cell

TINY = {"name": "tiny", "n_vectors": 4096, "d": 32, "n_queries": 200,
        "topk": 10, "seal_threshold": 1024,
        "hntl": {"k": 16, "s": 4, "pool": 20, "n_grains": 8, "nprobe": 16,
                 "mode": "B"},
        "data": {"intrinsic": 8, "curvature": 0.8, "noise": 0.05}}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2 ** 31 + 99


def tiny_cell(cell_name: str) -> registry.Cell:
    real = registry.load_cell(cell_name)
    return registry.Cell(name=real.name, chips=1, config=TINY,
                         traffic=real.traffic, limits=real.limits,
                         end_to_end=real.end_to_end, per_layer=[])


def run(cell_name, **kw):
    return run_cell(tiny_cell(cell_name), seed=SEED, seconds=0.3,
                    trace_on=False, device=CPU,
                    t_process=time.perf_counter(), **kw)


def stale(system):
    """A call that hands back the previous call's answer."""
    search, last = system.search, []

    def f(q):
        out = search(q)
        if last:
            out, last[0] = last[0], out
        else:
            last.append(out)
        return out
    return f


def half_batch(system):
    """Half of each batch left out, its rows filled from the other half."""
    search = system.search

    def f(q):
        h = (q.shape[0] + 1) // 2
        ids, d = search(q[:h])
        return np.concatenate([ids, ids])[:q.shape[0]], \
            np.concatenate([d, d])[:q.shape[0]]
    return f


def altered(system):
    """Each answer's last id altered where it is produced."""
    def f(q):
        ids, d = system.search(q)
        ids = ids.copy()
        ids[:, -1] = (ids[:, -1] + 1) % TINY["n_vectors"]
        return ids, d
    return f


def half_probes(system):
    """Half of the probes dropped: real rows, exact distances, the wrong
    candidates."""
    return lambda q: system.search(q, nprobe=TINY["hntl"]["nprobe"] // 2)


@pytest.mark.parametrize("cell", ["cohere768-1m.batch64",
                                  "cohere768-1m.serial"])
def test_sound_run_is_correct(cell):
    out = run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {m.name
                                   for m in registry.load_cell(cell)
                                   .end_to_end}


def test_bf16_control_is_not_correct():
    out = run("cohere768-1m.batch64", control=True)
    assert not out["correct"]
    assert out["checks"]["dist_err"]["value"] > \
        out["checks"]["dist_err"]["limit"]


@pytest.mark.parametrize("cell,fault", [
    ("cohere768-1m.batch64", stale), ("cohere768-1m.batch64", half_batch),
    ("cohere768-1m.batch64", altered), ("cohere768-1m.batch64", half_probes),
    ("cohere768-1m.serial", stale),
    ("cohere768-1m.serial", altered), ("cohere768-1m.serial", half_probes)],
    ids=["batch-stale", "batch-half", "batch-altered", "batch-half-probes",
         "serial-stale", "serial-altered", "serial-half-probes"])
def test_broken_timed_path_is_not_correct(cell, fault):
    assert not run(cell, wrap=fault)["correct"]


def test_fault_readings_reach_the_recall_limit():
    """What the limits were set from, at a test's size: the store's sound
    recall gap sits under the limit, and half the probes dropped reads
    above it."""
    lim = registry.load_cell("cohere768-1m.batch64").limits["recall_gap"]
    sound = run("cohere768-1m.batch64")["checks"]["recall_gap"]["value"]
    fault = run("cohere768-1m.batch64",
                wrap=half_probes)["checks"]["recall_gap"]["value"]
    assert sound <= lim < fault


def filtered_cell() -> registry.Cell:
    """The batch cell under a mix that keeps a tenth of the rows by ``ts``
    (the data makes ``ts`` a permutation of the row ids).  A filtered cell
    sets its recall limit from its own readings, so only the others hold
    here."""
    cell = tiny_cell("cohere768-1m.batch64")
    n = TINY["n_vectors"]
    cell.traffic = dict(cell.traffic,
                        search={"ts_range": [n // 2, n // 2 + n // 10]})
    cell.limits = {k: v for k, v in cell.limits.items() if k != "recall_gap"}
    return cell


def test_a_filtered_mix_is_checked_against_filtered_search():
    cell = filtered_cell()
    out = run_cell(cell, seed=SEED, seconds=0.3, trace_on=False,
                   device=CPU, t_process=time.perf_counter())
    assert out["correct"], out["checks"]
    # against the unfiltered truth nine in ten answers would miss
    assert out["metrics"]["recall_at_10"]["value"] > 0.9

    def unfiltered(system):
        return lambda q: system.search(q, ts_range=None)
    out = run_cell(cell, seed=SEED, seconds=0.3, trace_on=False,
                   device=CPU, t_process=time.perf_counter(), wrap=unfiltered)
    assert not out["correct"]
    assert out["checks"]["bad_rows"]["value"] > 0


def test_an_open_loop_mix_serves_every_arrival():
    """Requests arriving at a fixed rate, up to ``batch`` to a call: every
    arrival of the window is served, and latency runs from arrival."""
    cell = tiny_cell("cohere768-1m.batch64")
    cell.traffic = {"batch": 4, "rate_qps": 400.0}
    out = run_cell(cell, seed=SEED, seconds=0.5, trace_on=False,
                   device=CPU, t_process=time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["attempted"] == 200 and out["failed"] == 0
    assert out["metrics"]["latency_p50_ms"]["value"] > 0
