"""Benchmark harness: one module per paper table/claim.

  PYTHONPATH=src python -m benchmarks.run            # standard sizes
  PYTHONPATH=src python -m benchmarks.run --quick    # CI-scale
  PYTHONPATH=src python -m benchmarks.run --only table2_scan

A driver whose ``main(quick=...)`` returns a dict gets that dict written
to ``BENCH_<name>.json`` at the repo root (machine-readable QPS / recall /
latency / probe-count metrics; ``name`` is the module's ``BENCH_NAME``
attribute, defaulting to the module name).  Drivers that write their own
file and return None keep doing so.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

BENCHES = [
    ("table1_recall", "Paper Table 1: recall iso/aniso, Mode A/B, HNSW"),
    ("table2_scan", "Paper Table 2: Block-SoA vs AoS vs pointer-chase"),
    ("scan_select", "Fused scan→select: O(Q·pool) candidate state vs "
                    "full materialize, gather-free fused path"),
    ("cascade", "Mixed-precision cascade: int4/int8 bytes/vector <= 0.6x "
                "fixed, staged-budget recall, BENCH_cascade.json"),
    ("memory_footprint", "Paper 3.2: 66 B/vec vs HNSW graph bytes"),
    ("sift_scale", "Paper 4: SIFT-like scale recall/QPS/DRAM"),
    ("segment_scale", "LSM store: fused stacked search vs per-segment loop"),
    ("churn", "Mutation plane: QPS/recall under delete+upsert churn, "
              "compaction reclaim"),
    ("drift", "Maintenance plane: recall under streaming drift, frozen "
              "partition vs split/merge/refit"),
    ("shard_scale", "Distributed plane: QPS + per-shard scan work vs shards"),
    ("routing_adaptive", "Adaptive routing: hub-aware probing + per-query "
                         "early termination — probe counts + QPS at "
                         "iso-recall on a skewed mix, BENCH_routing.json"),
    ("serve_load", "Tenancy plane: many-tenant coalesced load — one "
                   "dispatch/window, zero re-stacks, zero leaks"),
    ("coldtier", "Tiered residency: paged cold-tier search bit-identical "
                 "to the all-warm plane, QPS floor at 25% hot set, "
                 "BENCH_coldtier.json"),
    ("hntl_kv_decode", "HNTL-KV retrieval decode vs exact attention"),
]


def _write_bench(name: str, payload: dict) -> None:
    out = os.path.join(os.path.dirname(__file__), "..",
                       f"BENCH_{name}.json")
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"--- wrote {os.path.relpath(out)}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)
    from repro.launch.cache import use_compile_cache
    use_compile_cache()

    failures = 0
    for name, desc in BENCHES:
        if args.only and name != args.only:
            continue
        print(f"\n=== {name}: {desc} ===")
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            ret = mod.main(quick=args.quick)
            if isinstance(ret, dict):
                _write_bench(getattr(mod, "BENCH_NAME", name), ret)
            print(f"--- {name} done in {time.time()-t0:.1f}s")
        except Exception:                                  # noqa: BLE001
            failures += 1
            print(f"--- {name} FAILED:\n{traceback.format_exc()}")
    print(f"\n{len(BENCHES) - failures}/{len(BENCHES)} benchmarks ok")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
