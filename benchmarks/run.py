"""Benchmark harness — one entry per paper table/figure.

Prints ``name,value,derived`` CSV lines.  Run:
    PYTHONPATH=src python -m benchmarks.run
"""

from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        accuracy_bitwidth,
        fig3_efficiency,
        kernel_bench,
        serve_throughput,
        softmax_fraction,
        table1_area_power,
    )

    suites = [
        ("softmax_fraction (paper §I motivation)", softmax_fraction.main),
        ("table1_area_power (paper Table I)", table1_area_power.main),
        ("fig3_efficiency (paper Fig 3)", fig3_efficiency.main),
        ("accuracy_bitwidth (paper §II precision)", accuracy_bitwidth.main),
        ("kernel_bench (kernels)", kernel_bench.main),
        ("serve_throughput (continuous batching)", serve_throughput.main),
    ]
    failures = []
    for name, fn in suites:
        print(f"# --- {name} ---", flush=True)
        t0 = time.time()
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failures.append(name)
        print(f"# ({time.time() - t0:.1f}s)", flush=True)
    if failures:
        print(f"# FAILED: {failures}")
        sys.exit(1)
    print("# all benchmarks passed")


if __name__ == "__main__":
    main()
