"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

- busy: the union of the intervals in which an operation ran on a chip
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), inside the
  window the benchmark's host span ``bench.window`` marks, averaged over
  the chips used;
- per jitted program (the ``XLA Modules`` line): device seconds and calls,
  averaged over the chips;
- the device operations that took most time, by self time (an operation
  that holds others, such as a ``while`` over the layers, keeps only the
  time none of them ran), named by their HLO name (``%fusion.12``);
- idle gaps: the stretches of the window in which the first chip ran
  nothing, each labelled by the innermost ``bench.*`` host span open at
  its middle (``bench.*`` spans are ``TraceAnnotation``s, on the trace's
  own clock), summed per label;
- per chip and per jitted program, its device seconds and the self time
  of the collective operations inside it (all-gather, reduce-scatter,
  all-reduce, all-to-all, collective-permute, by the operation's name or
  opcode: their ``-start``/``-done`` halves and fusions named after them,
  and the ``async-collective-start``/``-done`` fusions in which a v5e
  starts and ends an asynchronous all-gather).  An asynchronous
  collective shows on the ops line only where the chip waits for it: its
  start and done.  What overlaps them is other operations there (on a
  v5e, ``%fusion.N`` calling an ``%async_collective_fusion``: compute
  scheduled inside the gather), and the transfer itself is on the
  ``Async XLA Ops`` line, which is not read.  So this is the collectives'
  exposed time, and it lies within the program's.

The device planes run on a clock of their own.  Each program's start on the
device is matched with its ``DoEnqueueProgram`` on the host by ``run_id``,
and where a program seems to start before the host enqueued it, the
device times are moved so that the earliest start falls on its enqueue.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_SUFFIX = re.compile(r"\(\d+\)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
COLLECTIVE = re.compile(r"all-gather|reduce-scatter|all-reduce|all-to-all"
                        r"|collective-permute|async-collective")

Interval = Tuple[float, float]


@dataclass
class TraceSummary:
    busy_s: float
    window_s: float
    modules: Dict[str, Tuple[float, float]] = field(default_factory=dict)
    top_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)
    #: per chip used, per program: (device seconds, collective self seconds)
    chips: List[Dict[str, Tuple[float, float]]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module(self, prefix: str) -> Optional[Tuple[float, float]]:
        """(device seconds, calls) of the programs whose name starts with
        ``prefix``, or None."""
        hits = [v for k, v in self.modules.items() if k.startswith(prefix)]
        if not hits:
            return None
        return sum(h[0] for h in hits), sum(h[1] for h in hits)

    def breakdown(self) -> Dict[str, List[List]]:
        return {"device_ops": [[n, s] for n, s in self.top_ops[:10]],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:10]]}


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def short_name(hlo: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``%fusion.3``."""
    return hlo.split(" = ", 1)[0]


def opcode(hlo: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion`` ('' if none)."""
    parts = hlo.split(" = ", 1)
    m = _OPCODE.search(" " + parts[1]) if len(parts) == 2 else None
    return m.group(1) if m else ""


def is_collective(hlo: str) -> bool:
    return bool(COLLECTIVE.search(short_name(hlo))
                or COLLECTIVE.search(opcode(hlo)))


def own_times(evs: Sequence[Tuple[str, float, float]]
              ) -> List[Tuple[str, float, float, float]]:
    """(name, start, end, seconds not covered by an event nested inside
    it) of every event, in the order they close."""
    out: List[Tuple[str, float, float, float]] = []
    stack: List[List] = []          # [name, start, end, child seconds]

    def close(item):
        name, a, b, kids = item
        out.append((name, a, b, (b - a) - kids))
        if stack:
            stack[-1][3] += b - a

    for name, a, b in sorted(evs, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            close(stack.pop())
        stack.append([name, a, b, 0.0])
    while stack:
        close(stack.pop())
    return out


def self_times(evs: Sequence[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """Seconds per name not covered by an event nested inside it."""
    out: Dict[str, float] = {}
    for name, _, _, own in own_times(evs):
        out[name] = out.get(name, 0.0) + own
    return out


def collectives_by_program(owned: Sequence[Tuple[str, float, float, float]],
                          programs: Sequence[Tuple[str, float, float]]
                          ) -> Dict[str, float]:
    """Self seconds of the collective operations among ``owned`` (as
    ``own_times`` gives them, full HLO names), per program of ``programs``
    ((name, start, end) on the same clock) that holds the operation's
    middle, each clipped to the program's interval."""
    progs = sorted(programs, key=lambda p: p[1])
    starts = [a for _, a, _ in progs]
    out: Dict[str, float] = {}
    for name, a, b, own in owned:
        if not is_collective(name):
            continue
        i = bisect.bisect_right(starts, (a + b) / 2) - 1
        if i < 0 or progs[i][2] < (a + b) / 2:
            continue
        key, pa, pb = progs[i]
        out[key] = out.get(key, 0.0) + min(own, min(b, pb) - max(a, pa))
    return out


def _stat(event, key: str):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def clock_shift(profile, modules_line) -> float:
    """Seconds to add to device times to put them on the host's clock."""
    enq = {}
    for p in profile.planes:
        if p.name.startswith("/device:"):
            continue
        for line in p.lines:
            for e in line.events:
                if e.name == "DoEnqueueProgram":
                    rid = _stat(e, "run_id")
                    if rid is not None:
                        enq[int(rid)] = e.start_ns * 1e-9
    lags = []
    for e in modules_line.events if modules_line is not None else ():
        rid = _stat(e, "run_id")
        if rid is not None and int(rid) in enq:
            lags.append(e.start_ns * 1e-9 - enq[int(rid)])
    return max(0.0, -min(lags)) if lags else 0.0


def _device_planes(profile, device_ids: Optional[Sequence[int]]):
    planes = {}
    for p in profile.planes:
        if p.name.startswith(DEVICE_PREFIX):
            tail = p.name[len(DEVICE_PREFIX):]
            if tail.isdigit():
                planes[int(tail)] = p
    ids = sorted(planes) if device_ids is None else list(device_ids)
    missing = [i for i in ids if i not in planes]
    if missing or not ids:
        raise ValueError(f"trace has no device planes {missing or ids}; "
                         f"planes: {[p.name for p in profile.planes]}")
    return [planes[i] for i in ids]


def host_spans(profile) -> List[Tuple[str, float, float]]:
    out = []
    for p in profile.planes:
        if p.name.startswith("/device:"):
            continue
        for line in p.lines:
            out.extend(ev for ev in _events(line)
                       if ev[0].startswith(HOST_SPAN_PREFIX))
    return out


def _labeller(spans: Sequence[Tuple[str, float, float]]):
    """t -> the innermost ``bench.*`` span open at t (spans other than the
    window are sequential, or nested inside one another)."""
    inner = sorted((a, b, n) for n, a, b in spans if n != WINDOW_SPAN)
    starts = [a for a, _, _ in inner]

    def label(t: float) -> str:
        # the latest span to start before t is the innermost if it is open;
        # else one that encloses it started a little earlier
        i = bisect.bisect_right(starts, t) - 1
        for j in range(i, max(i - 8, -1), -1):
            a, b, n = inner[j]
            if a <= t <= b:
                return n
        return "outside bench spans"

    return label


def reduce(profile, device_ids: Optional[Sequence[int]] = None
           ) -> TraceSummary:
    spans = host_spans(profile)
    windows = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    lo, hi = windows[0][0], windows[-1][1]
    planes = _device_planes(profile, device_ids)
    n = len(planes)
    busy_total, modules, ops = 0.0, {}, {}
    chips: List[Dict[str, Tuple[float, float]]] = []
    first_busy: List[Interval] = []
    for i, plane in enumerate(planes):
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines:
            raise ValueError(f"{plane.name} has no {OPS_LINE!r} line; lines: "
                             f"{sorted(lines)}")
        shift = clock_shift(profile, lines.get(MODULES_LINE))
        evs = [(nm, a + shift, b + shift)
               for nm, a, b in _events(lines[OPS_LINE])]
        evs = [(nm, max(a, lo), min(b, hi)) for nm, a, b in evs
               if b > lo and a < hi]
        busy = union([(a, b) for _, a, b in evs])
        busy_total += sum(b - a for a, b in busy)
        if i == 0:
            first_busy = busy
        owned = own_times(evs)
        for nm, sec in self_times([(short_name(nm), a, b)
                                   for nm, a, b in evs]).items():
            ops[nm] = ops.get(nm, 0.0) + sec / n
        progs = []
        for nm, a, b in (_events(lines[MODULES_LINE])
                         if MODULES_LINE in lines else []):
            a, b = a + shift, b + shift
            if b > lo and a < hi:
                key = _SUFFIX.sub("", nm)
                progs.append((key, max(a, lo), min(b, hi)))
                sec, calls = modules.get(key, (0.0, 0.0))
                modules[key] = (sec + (min(b, hi) - max(a, lo)) / n,
                                calls + 1.0 / n)
        coll = collectives_by_program(owned, progs)
        chip: Dict[str, Tuple[float, float]] = {}
        for key, a, b in progs:
            sec, _ = chip.get(key, (0.0, 0.0))
            chip[key] = (sec + (b - a), coll.get(key, 0.0))
        chips.append(chip)
    idle: Dict[str, float] = {}
    label = _labeller(spans)
    for a, b in gaps(first_busy, lo, hi):
        lab = label((a + b) / 2)
        idle[lab] = idle.get(lab, 0.0) + (b - a)
    return TraceSummary(
        busy_s=busy_total / n, window_s=hi - lo, modules=modules,
        top_ops=sorted(ops.items(), key=lambda kv: -kv[1]),
        idle_gaps=sorted(idle.items(), key=lambda kv: -kv[1]), chips=chips)


def reduce_dir(log_dir: str, device_ids: Optional[Sequence[int]] = None
               ) -> TraceSummary:
    """Reduce the one ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``log_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace file under {log_dir}, found "
                         f"{files}")
    return reduce(ProfileData.from_file(files[0]), device_ids)
