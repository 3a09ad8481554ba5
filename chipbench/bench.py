"""One run of one cell: set-up, a measured window, then the check.

Set-up makes the corpus and queries on the device from the seed, builds
the store through its public calls and warms up the call shapes the
cell's traffic sends.  The window drives ``search`` as the mix says
(``traffic.py``) for the run's seconds and keeps every answer.  After it, with the store
freed, the exact reference decides ``correct`` over every answer.
"""
from __future__ import annotations

import math
import shutil
import sys
import tempfile
import time
from typing import Callable, Optional

import numpy as np

from . import data, reference, trace, traffic
from .registry import Cell
from .roofline import fused_select_work, peaks_for, routed_rows

TRACE_SECONDS = 4.0      # the traced part of a --trace 1 window
WARMUP_CALLS = 2
DRAIN_S = 60.0           # open loop: how long past the close waiting
                         # requests are still served


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts JAX's trace/lower/compile events while ``on``."""

    def __init__(self):
        import jax.monitoring
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, *_a, **_k) -> None:
        if self.on and event.startswith("/jax/core/compile"):
            self.count += 1


def measure(search: Callable, q_host: np.ndarray, gen, seconds: float,
            trace_dir: Optional[str] = None) -> dict:
    """One caller drives ``search`` for ``seconds`` and keeps every answer.

    Closed loop: the next call goes out when the last call's ids and
    distances are on the host, and a request's latency runs from its
    call's issue.  Open loop: requests arrive at the mix's fixed rate, each
    call takes every request waiting (up to ``batch``), and a request's
    latency runs from its arrival; arrivals stop at the window's close,
    and requests still waiting are served up to ``DRAIN_S`` past it (those
    left after that are ``failed``).  With ``trace_dir`` the first
    ``TRACE_SECONDS`` of the window are traced, each call inside a
    ``trace.SPAN`` annotation."""
    import jax
    lat, idxs, ids, dists = [], [], [], []
    traced = 0
    tracing = trace_dir is not None
    if tracing:
        jax.profiler.start_trace(trace_dir)
    t_start = time.perf_counter()
    t_last = t_start
    deadline = t_start + seconds
    due = math.ceil(seconds * gen.rate) if gen.open_loop else 0
    served = 0
    while True:
        if gen.open_loop:
            now = time.perf_counter()
            if served >= due or now >= deadline + DRAIN_S:
                break
            t_next = t_start + gen.arrival(served)
            if t_next > now:
                time.sleep(t_next - now)
                now = time.perf_counter()
            waiting = min(due, int((now - t_start) * gen.rate) + 1) - served
            n = min(max(waiting, 1), gen.batch)
            arrivals = [t_start + gen.arrival(served + j) for j in range(n)]
            idx = gen.next(n)
            q = q_host[idx]
        else:
            idx = gen.next()
            q = q_host[idx]
            arrivals = [time.perf_counter()] * len(idx)
            if arrivals[0] >= deadline:
                break
        if tracing:
            with jax.profiler.TraceAnnotation(trace.SPAN):
                i, d = search(q)
            traced += 1
        else:
            i, d = search(q)
        t_last = time.perf_counter()
        lat.extend(t_last - a for a in arrivals)
        served += len(idx)
        idxs.append(idx)
        ids.append(i)
        dists.append(d)
        if tracing and t_last - t_start >= min(TRACE_SECONDS, seconds):
            jax.profiler.stop_trace()
            tracing = False
    if tracing:
        jax.profiler.stop_trace()
    return dict(lat=np.asarray(lat), idx=idxs, ids=ids, dists=dists,
                elapsed=t_last - t_start, traced=traced,
                failed=max(due - served, 0))


def check(x: np.ndarray, q: np.ndarray, answers: dict, truth: np.ndarray,
          topk: int, keep: Optional[np.ndarray] = None) -> dict:
    """Every answer of the window against the exact reference.

    recall_gap  1 - Recall@10 over all requests, against exact search
    dist_err    largest relative gap between a returned distance and the
                float64 distance of the id it came with
    bad_rows    answers with an id that is no row (or none the mix's
                filter ``keep`` lets through), a repeated id, a distance
                that is not finite, or distances out of order
    """
    qidx = np.concatenate(answers["idx"])
    ids = np.concatenate(answers["ids"]).astype(np.int64)
    d = np.concatenate(answers["dists"]).astype(np.float64)
    n = x.shape[0]
    valid = (ids >= 0) & (ids < n)
    if keep is not None:
        valid &= keep[np.where(valid, ids, 0)]
    srt = np.sort(ids, axis=1)
    dup = np.any(srt[:, 1:] == srt[:, :-1], axis=1)
    bad = (~valid.all(axis=1) | dup | ~np.isfinite(d).all(axis=1)
           | np.any(np.diff(d, axis=1) < 0, axis=1)
           | (ids.shape[1] != topk))
    hits = reference.recall_hits(ids, truth[qidx])
    d64 = reference.host_sq_dists(x, q[qidx], ids)
    ok = valid & np.isfinite(d)
    err = np.abs(d - d64)[ok] / np.maximum(d64[ok], 1e-6)
    return {"recall_gap": float(1.0 - hits.sum() / (ids.shape[0] * topk)),
            "dist_err": float(err.max()) if err.size else math.inf,
            "bad_rows": int(bad.sum())}


def run_cell(cell: Cell, *, seed: int, seconds: float, trace_on: bool,
             device: dict, t_process: float, control: bool = False,
             wrap: Optional[Callable] = None) -> dict:
    """Everything after the look for a chip; returns the result line.
    ``wrap(system)``, where given, returns the search the window drives in
    place of ``system.search``: the tests break the timed path with it."""
    import jax
    cfg = cell.config
    topk = cfg["topk"]
    counter = CompileCounter()
    gen = traffic.Generator(cell.traffic, cfg["n_queries"], seed)

    t = time.perf_counter()
    x_dev, q_host, ts = data.make(seed, cfg)
    x_host = np.asarray(x_dev)
    x_dev.delete()
    keep = reference.keep_rows(ts, gen.search.get("ts_range"))
    log(f"data: {x_host.shape} corpus, {q_host.shape} queries "
        f"{time.perf_counter() - t:.1f}s")

    tmp = tempfile.mkdtemp(prefix="chipbench_")
    try:
        t = time.perf_counter()
        if control:
            system = reference.Bf16Search(x_host, topk, keep)
        else:
            from .system import StoreSystem
            system = StoreSystem(cfg, x_host, ts, cold_dir=tmp,
                                 search=gen.search)
        log(f"build: {time.perf_counter() - t:.1f}s")
        if hasattr(system, "routing_plane"):
            cents, sizes, cap = system.routing_plane()
            log(f"plane: {cents.shape[0]} grains, cap {cap}, "
                f"largest grain {int(sizes.max())} rows; seconds per "
                f"sealed segment {[round(t, 1) for t in system.seal_s]}")
        search = system.search if wrap is None else wrap(system)
        t = time.perf_counter()
        # every call size the window can send: ``batch``, or in an open
        # loop each size from 1 to ``batch``
        for n in (range(1, gen.batch + 1) if gen.open_loop else [gen.batch]):
            for _ in range(WARMUP_CALLS):
                search(q_host[gen.warmup()[:n]])
        log(f"warm-up: {time.perf_counter() - t:.1f}s")
        setup_s = time.perf_counter() - t_process

        trace_dir = tempfile.mkdtemp(prefix="trace_", dir=tmp) \
            if trace_on else None
        counter.on = True
        answers = measure(search, q_host, gen, seconds, trace_dir)
        counter.on = False
        n_req = sum(len(i) for i in answers["idx"])
        log(f"window: {len(answers['idx'])} calls, {n_req} queries in "
            f"{answers['elapsed']:.3f}s, {answers['failed']} not served; "
            f"compiles inside it: {counter.count}")
        stats = jax.devices()[0].memory_stats() or {}
        device = dict(device, memory_peak_bytes=int(
            stats.get("peak_bytes_in_use", 0)))

        layer, breakdown = {}, None
        if trace_on:
            layer, breakdown, device = read_trace(
                cell, gen, system, trace_dir, answers, q_host, device)
        system.close()
        del system, search
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    t = time.perf_counter()
    x_dev = jax.device_put(x_host)
    truth, _ = reference.exact_topk(x_dev, q_host, topk, keep=keep)
    x_dev.delete()
    nums = check(x_host, q_host, answers, truth, topk, keep)
    log(f"reference and check: {time.perf_counter() - t:.1f}s")

    checks = {k: {"value": nums[k], "limit": lim}
              for k, lim in cell.limits.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    lat = answers["lat"]
    e2e = {
        "qps": (n_req / answers["elapsed"], "queries/s"),
        "latency_p50_ms": (float(np.percentile(lat, 50)) * 1e3, "ms"),
        "latency_p95_ms": (float(np.percentile(lat, 95)) * 1e3, "ms"),
        "recall_at_10": (1.0 - nums["recall_gap"], "fraction"),
        "setup_s": (setup_s, "s"),
    }
    if trace_on:
        metrics = {m.name: {"value": layer[m.name], "unit": m.unit}
                   for m, _ in cell.per_layer if layer.get(m.name) is not None}
    else:
        metrics = {m.name: {"value": e2e[m.name][0], "unit": m.unit}
                   for m in cell.end_to_end}
    out = {"correct": bool(correct), "attempted": n_req + answers["failed"],
           "failed": answers["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compiles_in_window"] = counter.count
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}")
    out["checks"] = checks
    return out


def scan_work(cell: Cell, gen, system, answers: dict,
              q_host: np.ndarray) -> dict:
    """The scan→select work of the traced calls (``roofline``), from the
    benchmark's own exact routing over the store's grains; empty where the
    mix filters or probes adaptively, which that routing does not follow."""
    if not hasattr(system, "routing_plane") or \
            {"ts_range", "adaptive"} & set(gen.search):
        return {}
    cents, sizes, _ = system.routing_plane()
    h = cell.config["hntl"]
    probes = gen.search.get("nprobe", h["nprobe"])
    pool = gen.search.get("pool", h["pool"])
    ops_n = bytes_n = 0
    for idx in answers["idx"][:answers["traced"]]:
        probed, distinct = routed_rows(cents, sizes, q_host[idx], probes)
        o, b = fused_select_work(queries=len(idx), probes=probes,
                                 probed_rows=probed, distinct_rows=distinct,
                                 k=h["k"], s=h["s"], pool=pool)
        ops_n += o
        bytes_n += b
    return {"ops": ops_n, "bytes": bytes_n}


def read_trace(cell: Cell, gen, system, trace_dir: str, answers: dict,
               q_host: np.ndarray, device: dict):
    """Per-layer metrics, the breakdown and busy/window seconds from the
    traced part of the window."""
    ops, spans, host = trace.load(trace_dir)
    queries = sum(len(i) for i in answers["idx"][:answers["traced"]])
    v = trace.view(ops, spans, host, queries=queries,
                   work=scan_work(cell, gen, system, answers, q_host),
                   peaks=peaks_for(device["kind"]))
    layer = {m.name: read(v) for m, read in cell.per_layer}
    device = dict(device, busy_s=v.busy_s(), window_s=v.window_s)
    breakdown = {"device_ops": v.top_ops(10), "idle_gaps": v.idle_gaps(10)}
    log(f"trace: {len(spans)} call spans, {len(v.ops)} device ops, "
        f"busy {device['busy_s']:.4f}s of {device['window_s']:.4f}s")
    return layer, breakdown, device
