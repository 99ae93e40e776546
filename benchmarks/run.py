"""Benchmark harness: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV; exits non-zero if any bench
raised (its row reads ``ERROR``).

``--trace-out PATH`` enables span tracing for the whole harness and dumps
one Chrome ``trace_event`` JSON artifact (load in chrome://tracing or
Perfetto) covering every bench's spans.
"""

from __future__ import annotations

import sys
import time


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    trace_out = None
    if "--trace-out" in argv:
        trace_out = argv[argv.index("--trace-out") + 1]

    from repro.core import telemetry

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
    tracer = telemetry.get_tracer()
    errors = 0
    if trace_out:
        tracer.clear()
        tracer.start()
    try:
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
    finally:
        if trace_out:
            tracer.stop()
            tracer.write_chrome(trace_out)
            print(f"# wrote {len(tracer.events())} spans to {trace_out}",
                  file=sys.stderr)
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
