#!/usr/bin/env python3
"""Device time by model scope, and idle gaps by program span, read from a
profiler trace (``.xplane.pb``):

    python3 bench/scopes.py <trace.xplane.pb> [--device N ...]

- per jitted program, device self time by model scope: each operation's
  name stack (the ``tf_op`` stat of its event metadata, read from the raw
  ``XSpace``: ``jax.profiler.ProfileData`` does not expose event-metadata
  stats) reduced to its innermost ``jax.named_scope`` of ``SCOPES``,
  forward or backward (``transpose(...)`` in the stack).  An operation
  under no such scope is ``(no scope)`` (the layer scan's slices and
  stacking, XLA's copies that keep a name stack); one with no name stack
  is ``(unknown)``;
- idle gaps of the first chip labelled by the innermost program span
  (``core/telemetry`` spans, mirrored into the trace as annotations while
  the program's tracer is on) open at their middle on the dispatching
  thread: the host thread that called most jitted programs
  (``PjitFunction(...)`` events; the runtime enqueues them on a thread of
  its own).

Both read the stretch the ``bench.window`` span marks, or the whole trace
where it has none, with the clocks aligned as ``bench/trace.py`` aligns
them.  The benchmark's own reduction (``bench/trace.py``) does not call
this module: it deletes its trace once reduced, so this runs on a trace
kept by hand, such as one written by ``jax.profiler.trace`` around
``chip_smoke.py`` or by ``bench/tests/record_scopes.py``.
"""

from __future__ import annotations

import argparse
import bisect
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

if __package__ in (None, ""):               # run as a script
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace as tracelib  # noqa: E402

DISPATCH_EVENT = "PjitFunction("
#: a program span's name: dot-separated lowercase words, the subsystem
#: first, a bracketed index allowed on a word (``scan.group[3].fetch``)
_PROGRAM_SPAN = re.compile(r"^[a-z][a-z0-9_]*(\[\d+\])?"
                           r"(\.[a-z][a-z0-9_]*(\[\d+\])?)+$")
_PROGRAM_ID = re.compile(r"\((\d+)\)$")
_WRAPPED = re.compile(r"^([\w\-]+)\((.*)\)$")

#: the program's ``jax.named_scope`` names, one per model block
SCOPES = ("embed", "attention", "kv_write", "mlp", "moe", "ssm", "norm",
          "head", "optimizer")
NO_SCOPE = "(no scope)"
UNKNOWN = "(unknown)"
OUTSIDE = "outside program spans"

Stacks = Dict[str, Dict[Tuple[int, str], str]]


@dataclass
class ScopeSummary:
    #: program -> (device seconds, calls), averaged over the chips
    modules: Dict[str, Tuple[float, float]] = field(default_factory=dict)
    #: program -> {"fwd attention" | "bwd (no scope)" | "(unknown)" ...:
    #: device self seconds}, averaged over the chips
    scopes: Dict[str, Dict[str, float]] = field(default_factory=dict)
    #: idle gaps by the innermost program span open on the dispatching thread
    idle_by_program_span: List[Tuple[str, float]] = field(default_factory=list)

    def scope_share(self, prefix: str, scopes: Sequence[str]
                    ) -> Optional[float]:
        """Share, in %, of the device time of the programs named
        ``prefix...`` spent in ``scopes`` (forward and backward), or None
        where the trace holds no such program or its operations carry no
        model scope at all (a program built without named scopes)."""
        table: Dict[str, float] = {}
        for k, v in self.scopes.items():
            if k.startswith(prefix):
                for key, sec in v.items():
                    table[key] = table.get(key, 0.0) + sec
        seconds = sum(sec for k, (sec, _) in self.modules.items()
                      if k.startswith(prefix))
        if seconds <= 0 or not any(key.split(" ", 1)[-1] in SCOPES
                                   for key in table):
            return None
        inside = sum(sec for key, sec in table.items()
                     if key != UNKNOWN and key.split(" ", 1)[1] in scopes)
        return inside / seconds * 100


# ------------------------------------------------------------ name stacks
def _varint(buf, pos: int) -> Tuple[int, int]:
    out, shift = 0, 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def fields(buf):
    """(field number, value) of a serialized protobuf message: an int for
    a varint, a memoryview for a length-delimited or fixed-width field."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        kind = key & 7
        if kind == 0:
            val, pos = _varint(buf, pos)
        elif kind == 2:
            n, pos = _varint(buf, pos)
            val, pos = buf[pos:pos + n], pos + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            val, pos = buf[pos:pos + n], pos + n
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {pos}")
        yield key >> 3, val


def _map_value(entry):
    """The value message of a protobuf map entry (field 2)."""
    return next((v for f, v in fields(entry) if f == 2), b"")


def name_stacks(raw: bytes) -> Stacks:
    """Device plane name -> {(program id, operation's event name): its
    name stack}, from the ``tf_op`` stat of each event metadata of a
    serialized ``XSpace``.  The program id is 0 where the metadata has
    none.

    XSpace fields read: ``planes`` (1); XPlane ``name`` (2),
    ``event_metadata`` (4) and ``stat_metadata`` (5), maps of id to
    message; XEventMetadata ``name`` (2) and ``stats`` (5); XStatMetadata
    ``name`` (2); XStat ``metadata_id`` (1), ``uint64_value`` (3),
    ``str_value`` (5) and ``ref_value`` (7, the id of a stat metadata
    whose name is the string)."""
    out: Stacks = {}
    for f, plane in fields(memoryview(raw)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, val in fields(plane):
            if pf == 2:
                name = bytes(val).decode()
            elif pf == 4:
                events.append(_map_value(val))
            elif pf == 5:
                meta = dict(fields(_map_value(val)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not name.startswith(tracelib.DEVICE_PREFIX):
            continue
        ids = {v: k for k, v in stat_names.items()}
        tf_op, program_id = ids.get("tf_op"), ids.get("program_id")
        table = out.setdefault(name, {})
        for ev in events:
            ev_name, stack, pid = "", None, 0
            for ef, val in fields(ev):
                if ef == 2:
                    ev_name = bytes(val).decode()
                elif ef == 5:
                    stat = dict(fields(val))
                    sid = stat.get(1)
                    if sid == tf_op and tf_op is not None:
                        if 5 in stat:
                            stack = bytes(stat[5]).decode()
                        elif 7 in stat:
                            stack = stat_names.get(stat[7])
                    elif sid == program_id and program_id is not None:
                        pid = stat.get(3, 0)
            if stack:
                table[(pid, ev_name)] = stack
    return out


def _split_stack(stack: str) -> List[str]:
    """``a/b(c/d)/e`` -> ``[a, b(c/d), e]``: '/' inside brackets is kept."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(stack):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            parts.append(stack[start:i])
            start = i + 1
    parts.append(stack[start:])
    return parts


def scope_of(stack: Optional[str]) -> str:
    """``"fwd <scope>"`` or ``"bwd <scope>"`` of an operation's name stack
    (``<scope>`` its innermost of ``SCOPES``, else ``(no scope)``), or
    ``(unknown)`` without one.  Transform wrappers (``jvp(...)``,
    ``transpose(...)``) are unwrapped; ``transpose`` marks the backward
    pass, where the rematerialized forward also runs."""
    if not stack:
        return UNKNOWN
    if ":" in stack:                      # tf_op is "<name stack>:<op type>"
        stack = stack.rsplit(":", 1)[0]
    scope, backward = NO_SCOPE, False
    for part in _split_stack(stack):
        while True:
            m = _WRAPPED.match(part)
            if m is None:
                break
            backward = backward or m.group(1) == "transpose"
            part = m.group(2)
        for sub in _split_stack(part):
            if sub in SCOPES:
                scope = sub
    return f"{'bwd' if backward else 'fwd'} {scope}"


# ---------------------------------------------------------- program spans
def dispatch_spans(profile) -> List[Tuple[str, float, float]]:
    """The program spans on the host thread that called most jitted
    programs."""
    best, most = [], 0
    for p in profile.planes:
        if p.name.startswith("/device:"):
            continue
        for line in p.lines:
            evs = tracelib._events(line)
            n = sum(1 for ev in evs if ev[0].startswith(DISPATCH_EVENT))
            if n > most:
                best, most = evs, n
    return [ev for ev in best if _PROGRAM_SPAN.match(ev[0])
            and not ev[0].startswith(tracelib.HOST_SPAN_PREFIX)]


def innermost(spans: Sequence[Tuple[str, float, float]],
              times: Sequence[float], outside: str) -> List[str]:
    """For each of the ascending ``times``, the innermost of the nested
    ``spans`` of one thread open then, else ``outside``."""
    todo = sorted(spans, key=lambda s: (s[1], -s[2]))
    out, stack, i = [], [], 0
    for t in times:
        while i < len(todo) and todo[i][1] <= t:
            while stack and stack[-1][2] < todo[i][1]:
                stack.pop()
            stack.append(todo[i])
            i += 1
        while stack and stack[-1][2] < t:
            stack.pop()
        out.append(stack[-1][0] if stack else outside)
    return out


# --------------------------------------------------------------- reduction
def _scope_self_times(ops, module_evs, stacks, n: int,
                      out: Dict[str, Dict[str, float]]) -> None:
    """Adds each program's operation self time by scope (over ``n`` chips)
    to ``out``; an operation belongs to the program running at its start."""
    mods = sorted(module_evs, key=lambda e: e[1])
    starts = [a for _, a, _ in mods]
    keyed = []
    for full, a, b in ops:
        i = bisect.bisect_right(starts, a) - 1
        if i >= 0 and a < mods[i][2]:
            mod = mods[i][0]
            m = _PROGRAM_ID.search(mod)
            pid = int(m.group(1)) if m else 0
            prog = tracelib._SUFFIX.sub("", mod)
        else:
            pid, prog = 0, "(no program)"
        stack = stacks.get((pid, full))
        if stack is None:
            stack = stacks.get((0, full))
        keyed.append(((prog, scope_of(stack)), a, b))
    for (prog, scope), sec in tracelib.self_times(keyed).items():
        table = out.setdefault(prog, {})
        table[scope] = table.get(scope, 0.0) + sec / n


def reduce(profile, stacks: Stacks,
           device_ids: Optional[Sequence[int]] = None) -> ScopeSummary:
    """The scope table and the idle gaps by program span of a trace;
    ``stacks`` is ``name_stacks`` of the same trace."""
    planes = tracelib._device_planes(profile, device_ids)
    windows = [(a, b) for nm, a, b in tracelib.host_spans(profile)
               if nm == tracelib.WINDOW_SPAN]
    lo, hi = ((windows[0][0], windows[-1][1]) if windows
              else (float("-inf"), float("inf")))
    n = len(planes)
    modules: Dict[str, Tuple[float, float]] = {}
    scopes: Dict[str, Dict[str, float]] = {}
    first_busy: List[tracelib.Interval] = []
    for i, plane in enumerate(planes):
        lines = {ln.name: ln for ln in plane.lines}
        if tracelib.OPS_LINE not in lines:
            raise ValueError(f"{plane.name} has no {tracelib.OPS_LINE!r} "
                             f"line; lines: {sorted(lines)}")
        shift = tracelib.clock_shift(profile,
                                     lines.get(tracelib.MODULES_LINE))

        def clipped(line):
            return [(nm, max(a + shift, lo), min(b + shift, hi))
                    for nm, a, b in tracelib._events(line)
                    if b + shift > lo and a + shift < hi]

        ops = clipped(lines[tracelib.OPS_LINE])
        module_evs = (clipped(lines[tracelib.MODULES_LINE])
                      if tracelib.MODULES_LINE in lines else [])
        for nm, a, b in module_evs:
            key = tracelib._SUFFIX.sub("", nm)
            sec, calls = modules.get(key, (0.0, 0.0))
            modules[key] = (sec + (b - a) / n, calls + 1.0 / n)
        _scope_self_times(ops, module_evs, stacks.get(plane.name, {}), n,
                          scopes)
        if i == 0:
            first_busy = tracelib.union([(a, b) for _, a, b in ops])
    by_span: Dict[str, float] = {}
    if first_busy:
        idle = tracelib.gaps(first_busy, *((lo, hi) if windows else
                                           (first_busy[0][0],
                                            first_busy[-1][1])))
        labels = innermost(dispatch_spans(profile),
                           [(a + b) / 2 for a, b in idle], OUTSIDE)
        for (a, b), lab in zip(idle, labels):
            by_span[lab] = by_span.get(lab, 0.0) + (b - a)
    return ScopeSummary(
        modules=modules, scopes=scopes,
        idle_by_program_span=sorted(by_span.items(), key=lambda kv: -kv[1]))


def reduce_file(path: str, device_ids: Optional[Sequence[int]] = None
                ) -> ScopeSummary:
    from jax.profiler import ProfileData
    raw = Path(path).read_bytes()
    return reduce(ProfileData.from_serialized_xspace(raw), name_stacks(raw),
                  device_ids)


def lines(summary: ScopeSummary) -> List[str]:
    """The summary as text: one line per program, one for the idle gaps."""
    out = []
    for prog, table in sorted(summary.scopes.items()):
        total = summary.modules.get(prog, (sum(table.values()), 0.0))[0]
        rows = ", ".join(f"{k} {s:.4f}s ({s / total * 100:.1f}%)"
                         for k, s in sorted(table.items(),
                                            key=lambda kv: -kv[1]))
        out.append(f"scopes of {prog} ({total:.4f}s on the device): {rows}")
    out.append("idle gaps by program span: " + ", ".join(
        f"{k} {s:.4f}s" for k, s in summary.idle_by_program_span))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="a .xplane.pb file")
    ap.add_argument("--device", type=int, nargs="*", default=None,
                    help="TPU ids to read (default: every one)")
    args = ap.parse_args(argv)
    for line in lines(reduce_file(args.trace, args.device)):
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
