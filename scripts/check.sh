#!/usr/bin/env bash
# One-command gate: tier-1 test suite + TQL pruning/coalescing benchmark
# (smoke mode, incl. the top-k gate: ORDER BY + LIMIT must fetch <= half
# the legacy chunk groups, sketch-pruned membership queries must issue
# zero payload requests, and the aggregation-pushdown gate: ungrouped
# COUNT/SUM/MIN/MAX/AVG over committed stats answers with zero payload
# requests, grouped streaming aggregation value-identical to the legacy
# whole-view fold at strictly fewer requests) + cold-open budget & maintenance smoke (backfill
# -> prune-parity, GC dry-run, compaction) + fig6 streaming smoke with a
# stall-seconds budget (cross-unit prefetch must keep compute the
# bottleneck) + chaos smoke (seeded storage faults: byte-identical stream
# results, visible retry/hedge counters, request amplification <= 1.5x,
# and the write plane: 4 concurrent committers under injected put/cas
# faults with zero lost appends, byte-parity vs a serial run, zero
# stranded chunk bytes, and wasted uploads == 0 on non-overlapping
# contention, plus traced fetch.retry/fetch.hedge/commit.rebase spans)
# + serving smoke (8-client same-query storm <= 2x one client's requests,
# distinct-query storm sublinear, shard-parallel scan byte-parity,
# repeat-query cache hit with zero planner work / zero requests, tracing
# overhead <= 5% with serve.admit / serve.shard[k] spans recorded)
# + telemetry gates (fig6 stall-attribution causes sum to total, traced
# run's sim seconds within 5% of untraced, with scan.group spans)
# + BENCH_io.json validation (incl.
# the stall_attribution section) + no-tracked-bytecode guard.
# Usage: scripts/check.sh  (from the repo root)
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== hygiene: no tracked bytecode =="
if git ls-files '*.pyc' '*.pyo' | grep -q .; then
  echo "ERROR: compiled bytecode files are tracked:" >&2
  git ls-files '*.pyc' '*.pyo' >&2
  exit 1
fi

echo "== tier-1: pytest =="
python -m pytest -x -q

echo "== TQL pruning + coalesced-I/O benchmark (smoke) =="
python -m benchmarks.bench_tql --smoke

echo "== cold-open budget + maintenance smoke =="
python -m benchmarks.bench_maintenance --smoke

echo "== fig6 streaming smoke (stall budget + attribution + tracing overhead) =="
python -m benchmarks.bench_fig6_streaming_train --smoke

echo "== chaos smoke (hostile-storage parity + amplification + write-chaos gates) =="
python -m benchmarks.bench_chaos --smoke

echo "== serving smoke (N-client storms + shard parity + versioned cache) =="
python -m benchmarks.bench_serving --smoke

echo "== BENCH_io.json validation =="
python -m benchmarks.io_report --validate

echo "== check.sh: all green =="
