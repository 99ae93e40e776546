"""The benchmark's harness: resolves a cell from ``BENCHMARK.json`` and the
files beside it, runs the cell's driver, reads its per-layer metrics and
prints the result line.

Everything that belongs to one configuration, traffic mix or per-layer metric
is a file of its own, found by name:

- ``bench/configs/<file>``: a configuration (named in ``configs[].file``);
- ``bench/traffic/<traffic>.json``: a traffic mix; its ``kind`` picks the
  driver ``bench/drivers/<kind>.py``;
- ``bench/metrics/<metric>.py``: a per-layer metric, a ``read(rec)`` that
  returns a number or ``None`` when the run holds nothing to read;
- ``bench/flops/<family>.py``: operations and bytes of a model family;
- ``bench/reference/<name>.py``: the plain float32 reference that decides
  ``correct``, named by the configuration's ``reference`` key.  It imports
  nothing of the program and keeps this contract:

  - ``Sizes.of(cfg)``: the sizes it needs, from the configuration file; the
    object is handed back as the first argument of the functions below;
  - ``train_steps(sizes, opt, seed, batches, dot=..., against=None,
    keep_grad=False, devices=None)`` (train cells): AdamW from the seed's
    weights, one step per batch, returning ``losses``, ``grad_norms``
    (per leaf, the first gradient as AdamW took it) and ``change_norms``
    (per leaf, the weights' change after the last step); with
    ``against`` (a first gradient per leaf on the host) also
    ``grad_diff_norms``, with ``keep_grad`` also ``grad``.  Its weights,
    gradient and moments live sharded over ``devices`` (default: the
    default device alone);
  - ``served_gaps(sizes, seed, seqs, prompt_len, dot=...)`` (serve cells):
    at each served token of ``seqs`` (prompt and served tokens), how far
    the reference's logit of the token lies below its best;
  - ``CONTROLS``: the control's name to the product (``dot``) the two
    functions take in place of their own (read by ``bench/control.py``).

Adding a cell therefore needs new files and a new ``workloads`` entry only.
"""

from __future__ import annotations

import importlib.util
import json
import math
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


class BenchError(Exception):
    """A cell that cannot be resolved or run as specified."""


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    traffic_name: str
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    #: the configuration's reference module (``reference_for``)
    reference: Any = None


@dataclass
class RunContext:
    """What a driver is handed: the cell, the seed, the window and the
    devices it may use."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: List[Any]
    #: hook for the CPU tests, called with the driver's live objects
    #: before the window opens (never set by the chip command)
    patch: Optional[Callable[[Dict[str, Any]], None]] = None

    @property
    def jax_seed(self) -> int:
        return jax_seed(self.seed)


@dataclass
class Record:
    """What a driver hands back: end-to-end metrics, what the per-layer
    readers read, and the numbers compared for ``correct``."""
    metrics: Dict[str, float]
    attempted: int
    failed: int
    memory_peak_bytes: int
    checks: Dict[str, Dict[str, float]]
    layer: Dict[str, Any] = field(default_factory=dict)
    trace: Optional[Any] = None           # bench.trace.TraceSummary

    @property
    def correct(self) -> bool:
        return all(c["value"] <= c["limit"] for c in self.checks.values()) \
            and self.failed == 0


def jax_seed(seed: int) -> int:
    """A 31-bit seed for ``jax.random.PRNGKey`` and the program's own seeds:
    PRNGKey keeps only the low 32 bits of a larger int, so seeds differing
    above them would collide."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0] >> 1)


def load_json(path: Path) -> Any:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def load_module(path: Path):
    """Import a harness file by path (metric names may hold '.' or '-')."""
    if not path.is_file():
        raise BenchError(f"no such file: {path}")
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod        # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def _for_cell(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(spec: Dict[str, Any], name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of a parsed ``BENCHMARK.json``, with its files."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"workload {name!r} names unknown config "
                         f"{w['config']!r}")
    config = load_json(root / configs[w["config"]]["file"])
    reference = reference_for(config, root)
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in spec["end_to_end"] if _for_cell(m, name)]
    e2e_names = {m["name"] for m in e2e}
    if "setup_s" not in e2e_names:
        raise BenchError(f"workload {name!r} does not report setup_s")
    per_layer = [m for m in spec["per_layer"] if _for_cell(m, name)]
    for m in per_layer:
        if m["moves"] not in e2e_names:
            raise BenchError(
                f"per-layer metric {m['name']!r} moves {m['moves']!r}, which "
                f"workload {name!r} does not report")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, traffic_name=w["traffic"], end_to_end=e2e,
                per_layer=per_layer, reference=reference)


def driver_for(cell: Cell, root: Path = ROOT):
    kind = cell.traffic.get("kind")
    if not isinstance(kind, str) or not kind.isidentifier():
        raise BenchError(f"traffic {cell.traffic_name!r} has no valid kind")
    return load_module(root / "bench" / "drivers" / f"{kind}.py")


def flops_for(family: str, root: Path = ROOT):
    return load_module(root / "bench" / "flops" / f"{family}.py")


def reference_for(config: Dict[str, Any], root: Path = ROOT):
    """The reference module a configuration names; one that names none, or
    one that is not there, is refused."""
    name = config.get("reference")
    if not isinstance(name, str) or not name.isidentifier():
        raise BenchError(f"config of arch {config.get('arch')!r} names no "
                         f"valid reference: {name!r}")
    return load_module(root / "bench" / "reference" / f"{name}.py")


def peaks(device_kind: str, root: Path = ROOT) -> Dict[str, float]:
    """Published peaks of one chip; a kind not in the table is an error."""
    table = load_json(root / "bench" / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(table)}")
    return table[device_kind]


def require_chips(chips: int) -> List[Any]:
    """The TPU devices a cell runs on.  Any other platform, or fewer chips
    than the cell asks for, ends the run with no result."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX found platform "
                         f"{platform!r} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips; JAX found "
                         f"{len(devices)}")
    return devices[:chips]


# ------------------------------------------------------------------ numbers
def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def spans_of(ends: List[float], start: float, min_span: float = 0.25
             ) -> List[float]:
    """Seconds per step over consecutive spans of whole steps, each at
    least ``min_span`` long: a time read from the host clock is off by some
    half a millisecond, so none is shorter than a quarter second.
    ``ends`` are the steps' end times, ``start`` the first step's start."""
    out, t0, n = [], start, 0
    for t in ends:
        n += 1
        if t - t0 >= min_span:
            out.append((t - t0) / n)
            t0, n = t, 0
    return out


# ------------------------------------------------------------------ result
def read_per_layer(cell: Cell, rec: Record, root: Path = ROOT
                   ) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in cell.per_layer:
        value = load_module(root / "bench" / "metrics" / f"{m['name']}.py"
                            ).read(rec)
        if value is None:
            continue
        if not math.isfinite(value):
            raise BenchError(f"per-layer metric {m['name']} read {value}")
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(cell: Cell, rec: Record, trace: bool, device: Dict[str, Any],
                root: Path = ROOT) -> Dict[str, Any]:
    if trace:
        metrics = read_per_layer(cell, rec, root)
    else:
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in rec.metrics:
                raise BenchError(f"the driver did not measure {m['name']}")
            metrics[m["name"]] = {"value": float(rec.metrics[m["name"]]),
                                  "unit": m["unit"]}
    dev = dict(device)
    dev["memory_peak_bytes"] = int(rec.memory_peak_bytes)
    line: Dict[str, Any] = {"correct": rec.correct, "attempted": rec.attempted,
                            "failed": rec.failed, "metrics": metrics,
                            "device": dev}
    if trace and rec.trace is not None:
        dev["busy_s"] = rec.trace.busy_s
        dev["window_s"] = rec.trace.window_s
        line["breakdown"] = rec.trace.breakdown()
    line["checks"] = rec.checks
    return line


def print_checks(rec: Record) -> None:
    for name, c in rec.checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices: List[Any], *, root: Path = ROOT,
             patch: Optional[Callable[[Dict[str, Any]], None]] = None
             ) -> Dict[str, Any]:
    """Run one cell on ``devices`` and return its result line."""
    ctx = RunContext(cell=cell, seed=seed, seconds=seconds, trace=trace,
                     devices=list(devices), patch=patch)
    rec = driver_for(cell, root).run(ctx)
    dev = devices[0]
    import jax
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    line = result_line(cell, rec, trace, device, root)
    print_checks(rec)
    return line
