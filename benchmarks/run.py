"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows; a suite name (or part of
one) as the first argument runs only the matching suites.
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_ordering, bench_scores, bench_buffer_size, bench_batch_size,
        bench_restream, bench_sota, bench_gnn_comm,
    )

    suites = [
        ("fig1_ordering", bench_ordering.run),
        ("fig4_scores", bench_scores.run),
        ("fig5_buffer_size", bench_buffer_size.run),
        ("fig6_batch_size", bench_batch_size.run),
        ("table2_restream", bench_restream.run),
        ("fig7_sota", bench_sota.run),
        ("gnn_comm", bench_gnn_comm.run),
    ]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    t_all = time.time()
    for name, fn in suites:
        if only and only not in name:
            continue
        t0 = time.time()
        fn(verbose=True)
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    print(f"# all benchmarks done in {time.time() - t_all:.1f}s")


if __name__ == "__main__":
    main()
