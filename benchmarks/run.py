"""Benchmark harness: one module per paper table/figure + the roofline.

    PYTHONPATH=src python -m benchmarks.run                     # all
    PYTHONPATH=src python -m benchmarks.run strassen            # one
    PYTHONPATH=src python -m benchmarks.run --quick dag_overhead serving
                                 # several (one combined results file)

``--quick`` shrinks problem sizes / repetitions for CI smoke runs; numbers
from quick mode are sanity signals, not trajectory data.

Prints ``bench,key-fields...`` lines and writes
benchmarks/results/bench_results.json.  The dag_overhead suite additionally
writes ``benchmarks/BENCH_dag_overhead.json`` — the committed,
machine-readable before/after executor trajectory (interpreter vs compiled
plan vs pluggable backends) that future PRs append their numbers to.
"""

from __future__ import annotations

import json
import os
import sys


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_strassen, bench_distgemm, bench_sort, bench_dag_overhead,
        bench_roofline, bench_serving)

    args = [a for a in sys.argv[1:] if a != "--quick"]
    quick = "--quick" in sys.argv[1:]
    suites = {
        "strassen": lambda: bench_strassen.run(),
        "distgemm": lambda: bench_distgemm.run(),
        "sort": lambda: bench_sort.run(n_items=100_000 if quick else 1_000_000),
        "dag_overhead": lambda: bench_dag_overhead.run(quick=quick),
        "serving": lambda: bench_serving.run(quick=quick),
        "roofline": lambda: bench_roofline.run(mesh=None),
    }
    if args and "all" not in args:
        # several names combine into one run (and one results file) —
        # single-suite invocations would overwrite each other's rows
        suites = {name: suites[name] for name in args}

    all_rows = []
    for name, fn in suites.items():
        print(f"== {name} ==", flush=True)
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001
            print(f"{name} FAILED: {e!r}")
            raise
        for r in rows:
            print(",".join(f"{k}={v}" for k, v in r.items()), flush=True)
        all_rows.extend(rows)

    out = os.path.join(os.path.dirname(__file__), "results",
                       "bench_results.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(all_rows, f, indent=1, default=str)
    print(f"\nwrote {len(all_rows)} rows -> {out}")

    dag_rows = [r for r in all_rows
                if r.get("bench") in ("dag_overhead", "backend_parallel",
                                      "backend_parallel_procs",
                                      "procs_calibration",
                                      "chain_fused", "binop_chain_fused",
                                      "stitched_chain_fused",
                                      "mesh_chain_pallas",
                                      "versioning_memory",
                                      "fault_recovery", "serving")]
    if quick and dag_rows:
        # quick numbers are smoke signals, never trajectory data — keep the
        # committed BENCH_dag_overhead.json untouched
        print("(--quick: skipping BENCH_dag_overhead.json update)")
    elif dag_rows:
        dag_out = os.path.join(os.path.dirname(__file__),
                               "BENCH_dag_overhead.json")
        with open(dag_out, "w") as f:
            json.dump(dag_rows, f, indent=1, default=str)
        print(f"wrote {len(dag_rows)} rows -> {dag_out}")


if __name__ == "__main__":
    main()
