"""Distributed search plane: queries/sec and per-shard scan work vs shards.

The claim under test: because grains are independent fixed-shape blocks
with no cross-grain pointers, partitioning the fused plane by grain needs
no graph cutting — per-shard scan work (probed grains x slots per shard)
drops as shards are added while the only cross-shard traffic is ONE
all-gather of the per-shard top-k pools.  Acceptance floor: per-shard scan
work strictly decreases from 1 to the max shard count.

Wall-clock QPS is also reported but is NOT the headline on this harness:
forced host devices carve one CPU into n logical devices that share the
same cores, so sharding pays collective overhead without adding FLOPs.  On
real multi-chip meshes the per-shard work column is the wall-clock story.

Runs in a subprocess on the CPU with forced host devices (the device
count must be fixed before jax initializes, and the child must never touch
the accelerator its parent process may already hold):

  PYTHONPATH=src python -m benchmarks.shard_scale [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

BENCH_NAME = "shard_scale"
_JSON_MARK = "BENCH_JSON "      # child -> parent result hand-off line


def _child(quick: bool):
    import numpy as np

    from repro.core import HNTLConfig
    from repro.core.store import VectorStore
    from repro.data import synthetic as syn
    from repro.launch.mesh import make_host_mesh

    n_total = 16384 if quick else 65536
    d, nq, seg_rows = 64, 32, n_total // 8
    iters = 5 if quick else 10
    cfg = HNTLConfig(d=d, k=16, s=0, n_grains=16, nprobe=8, pool=32,
                     block=64)
    st = VectorStore(cfg, seal_threshold=seg_rows)
    x = syn.clustered(n_total, d, n_clusters=32, seed=0)
    for lo in range(0, n_total, seg_rows):
        st.add(x[lo:lo + seg_rows])
    rng = np.random.default_rng(1)
    q = (x[rng.integers(0, n_total, nq)]
         + 0.05 * rng.standard_normal((nq, d))).astype(np.float32)
    total_grains = sum(s.index.grains.n_grains for s in st._segments)
    # scan-bound regime: probe the whole plane, so the probed-slot count per
    # shard is the honest "scan work" metric (nprobe is per-shard and
    # clamped to each shard's grain slice)
    nprobe = total_grains

    def timed(fn, iters):
        for _ in range(2):
            fn()
        t0 = time.perf_counter()
        for _ in range(iters):
            fn()
        return (time.perf_counter() - t0) / iters

    rows = []
    for shards in (1, 2, 4, 8):
        mesh = make_host_mesh(1, shards) if shards > 1 else None
        if mesh is not None:
            plane = st._sharded_for(tuple(st._segments), mesh,
                                    "model")["plane"]
            g_local = plane.index.grains.n_grains // shards
            cap = plane.index.grains.cap
        else:
            stacked = st._stacked_for(tuple(st._segments))["plane"]
            g_local = stacked.index.grains.n_grains
            cap = stacked.index.grains.cap
        probe = min(nprobe, g_local)
        work = probe * cap
        t = timed(lambda: st.search(q, topk=10, mode="B", mesh=mesh,
                                    nprobe=nprobe), iters)
        rows.append({"shards": shards, "qps": nq / t,
                     "probed_grains_per_shard": probe,
                     "scan_slots_per_shard": work})
        print(f"  shards={shards}  {nq / t:9.1f} q/s   "
              f"{probe:4d} grains/shard   {work:7d} scan slots/shard")
    works = [r["scan_slots_per_shard"] for r in rows]
    assert all(a > b for a, b in zip(works, works[1:])), \
        f"per-shard scan work must decrease with shard count: {works}"
    print("per-shard scan work strictly decreases: "
          + " > ".join(str(w) for w in works))
    payload = {"quick": quick, "n_total": n_total, "d": d,
               "n_queries": nq,
               "rows": [{k: round(v, 3) if isinstance(v, float) else v
                         for k, v in r.items()} for r in rows],
               "scan_work_strictly_decreasing": True}
    print(_JSON_MARK + json.dumps(payload))
    return rows


def main(quick: bool = False):
    """Spawn the sweep with 8 forced host devices (fresh jax, CPU only)."""
    print("shards, qps, probed grains/shard, scan slots/shard")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "benchmarks.shard_scale", "--child"]
    if quick:
        cmd.append("--quick")
    out = subprocess.run(cmd, env=env, cwd=os.path.dirname(src),
                         capture_output=True, text=True, timeout=1800)
    payload = None
    for line in out.stdout.splitlines():
        if line.startswith(_JSON_MARK):
            payload = json.loads(line[len(_JSON_MARK):])
        else:
            print(line)
    if out.returncode != 0:
        raise RuntimeError(f"shard_scale child failed:\n{out.stderr}")
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--child", action="store_true",
                    help="internal: run the sweep in this process")
    args = ap.parse_args()
    if args.child:
        _child(args.quick)
    else:
        main(quick=args.quick)
