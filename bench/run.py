#!/usr/bin/env python3
"""Chip benchmark of the lakehouse's train and serve paths.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the TPU chips of this machine, from
the root of a checkout.  It never falls back to the CPU: on any other
platform, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.  Set-up (corpus, store, weights, warm-up of the cell's
own shapes) is timed as ``setup_s``; then the window runs for ``--seconds``
with nothing compiling inside it.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics, read from the
profiler's trace of the window and from the program's spans and counters.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``, each number compared for ``correct``
beside its limit.  The same checks are the last lines of standard error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # libtpu logs to a fixed /tmp path unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import repro  # noqa: F401  the system under test: without it, no run
    from bench import harness

    spec = harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.resolve_cell(spec, args.workload, ROOT)
    devices = harness.require_chips(cell.chips)
    line = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            devices, root=ROOT)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
