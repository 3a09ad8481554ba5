#!/usr/bin/env python3
"""On-chip benchmark of the HNTL vector store: one run of one cell.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
      --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a deployment
(``chipbench/configs/``) under a traffic mix (``chipbench/traffic/``).
The run makes its data from the seed, builds the store, warms up, then
drives ``VectorStore.search`` for ``--seconds`` and checks every answer
against exact search.  With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the first seconds of the window.  The last line of
standard output is the result as one JSON object; the numbers compared
for ``correct`` end standard error and the result line.

``--control`` puts the reference, computed in bfloat16, in the store's
place; it exists to show that the check fails it.

With no TPU, or fewer chips than the cell asks for, the run exits
nonzero before any set-up and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
CACHE_DIR = ROOT / ".jax_cache"


def tpu_device(chips: int) -> dict:
    """The chip the run uses, as JAX reports it, or exit nonzero."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"chipbench: JAX found no devices: {e}")
    if devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU; JAX found "
                         f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"found {len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips}


def use_compile_cache() -> None:
    """JAX's persistent compile cache at the fixed ``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` names one; every program is kept,
    however quickly it compiled, so a second run compiles nothing."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the bfloat16 reference in the store's place")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench.registry import load_cell
    cell = load_cell(args.workload)
    device = tpu_device(cell.chips)
    use_compile_cache()
    import repro.core.store  # noqa: F401 -- the system under test

    from chipbench.bench import log, run_cell
    log(f"cell {cell.name}: {cell.config['name']} x "
        f"{cell.traffic['batch']}-query calls, seed {args.seed}, "
        f"{args.seconds}s, trace {args.trace}, device {device}")
    out = run_cell(cell, seed=args.seed, seconds=args.seconds,
                   trace_on=bool(args.trace), device=device,
                   t_process=T_PROCESS, control=args.control)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
