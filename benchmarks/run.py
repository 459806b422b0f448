"""Benchmark orchestrator: one entry per paper table/figure + system benches.

``PYTHONPATH=src python -m benchmarks.run [names...]``

Each bench prints its own tables; this driver wraps them with timing and a
final ``name,seconds,status`` CSV summary so partial failures are visible
without killing the run.
"""

from __future__ import annotations

import importlib
import sys
import time
import traceback

# name -> module with a run() entry point
BENCHES = [
    ("paper_tables", "benchmarks.paper_tables"),  # Fig 3 a-l analogue
    ("metagraph_accuracy", "benchmarks.metagraph_accuracy"),  # s3.2 claims
    ("delta_sweep", "benchmarks.delta_sweep"),  # beyond-paper granularity
    ("bc_workload", "benchmarks.bc_workload"),  # s7 future work: BC waves
    ("traversal", "benchmarks.traversal_bench"),  # engine perf -> BENCH_traversal.json
    ("strategy_scaling", "benchmarks.strategy_scaling"),  # s5 complexity claims
    ("kernel_bench", "benchmarks.kernel_bench"),  # Pallas kernels vs refs
    ("roofline", "benchmarks.roofline"),  # dry-run roofline summary
]


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    want = set(sys.argv[1:])
    summary = []
    for name, module in BENCHES:
        if want and name not in want:
            continue
        print(f"\n=== {name} " + "=" * max(0, 60 - len(name)))
        t0 = time.perf_counter()
        try:
            mod = importlib.import_module(module)
            mod.run()
            status = "ok"
        except Exception:
            traceback.print_exc()
            status = "FAILED"
        summary.append((name, time.perf_counter() - t0, status))

    print("\nname,seconds,status")
    for name, secs, status in summary:
        print(f"{name},{secs:.1f},{status}")
    if any(s == "FAILED" for _, _, s in summary):
        sys.exit(1)


if __name__ == "__main__":
    main()
