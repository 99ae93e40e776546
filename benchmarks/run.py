"""Benchmark harness: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV; exits non-zero if any bench
raised (its row reads ``ERROR``).
"""

from __future__ import annotations

import sys
import time


def main() -> int:
    from . import (bench_chaos, bench_fig5_formats,
                   bench_fig6_streaming_train, bench_fig7_utilization,
                   bench_kernels, bench_tql)
    modules = [
        ("fig5_formats", bench_fig5_formats),
        ("fig6_streaming_train", bench_fig6_streaming_train),
        ("fig7_utilization", bench_fig7_utilization),
        ("tql", bench_tql),
        ("kernels", bench_kernels),
        ("chaos", bench_chaos),
    ]
    errors = 0
    print("name,us_per_call,derived")
    for name, mod in modules:
        t0 = time.perf_counter()
        try:
            for line in mod.main():
                print(line, flush=True)
        except Exception as e:  # keep the harness running
            errors += 1
            print(f"{name},ERROR,{type(e).__name__}:{e}", flush=True)
        print(f"# {name} done in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
