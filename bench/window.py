"""What every driver's measured window shares: the compile cache, a count
of compilations, host spans on the profiler's clock, the device trace and
the memory peak."""

from __future__ import annotations

import contextlib
import gc
import resource
import shutil
import tempfile
from typing import Any, List, Optional

import jax

from . import trace as tracelib

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


def use_cache() -> str:
    """The program's persistent compile cache, for every program however
    quick to compile, so that a second run of a cell compiles nothing."""
    from repro.launch.compile_cache import use_compile_cache
    where = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCounter:
    """Counts traces and backend compiles while ``active``."""

    def __init__(self) -> None:
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, secs: float, **kw: Any) -> None:
        if self.active and name in COMPILE_EVENTS:
            self.count += 1


def span(name: str, on: bool):
    """A host span on the profiler's clock when tracing, else nothing."""
    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


class DeviceTrace:
    """The profiler over the first ``seconds`` of the window, when on.
    ``summary`` is the reduced trace once stopped."""

    def __init__(self, on: bool, devices: List[Any], seconds: float) -> None:
        self.on, self.devices, self.seconds = on, devices, seconds
        self.summary: Optional[tracelib.TraceSummary] = None
        self._dir: Optional[str] = None
        self._span: Optional[Any] = None

    def start(self) -> None:
        if self.on:
            self._dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self._dir)
            self._span = jax.profiler.TraceAnnotation(tracelib.WINDOW_SPAN)
            self._span.__enter__()

    def due(self, elapsed: float) -> bool:
        return self._span is not None and elapsed >= self.seconds

    def stop(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
            jax.profiler.stop_trace()

    def summarize(self) -> Optional[tracelib.TraceSummary]:
        """Reduce the trace (after the window: reading it takes time)."""
        if self._dir is not None:
            try:
                self.summary = tracelib.reduce_dir(
                    self._dir, [d.id for d in self.devices])
            finally:
                shutil.rmtree(self._dir, ignore_errors=True)
                self._dir = None
        return self.summary


@contextlib.contextmanager
def quiet_host():
    """No cyclic garbage collection inside the window: what set-up made is
    frozen out of it, and nothing is collected until the window ends."""
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()
        gc.unfreeze()


def host_usage():
    """This process's CPU seconds, page faults and context switches."""
    return resource.getrusage(resource.RUSAGE_SELF)


def usage_delta(a, b) -> str:
    return (f"cpu {b.ru_utime + b.ru_stime - a.ru_utime - a.ru_stime:.2f} s, "
            f"faults {b.ru_minflt - a.ru_minflt} minor "
            f"{b.ru_majflt - a.ru_majflt} major, switches "
            f"{b.ru_nvcsw - a.ru_nvcsw} voluntary "
            f"{b.ru_nivcsw - a.ru_nivcsw} involuntary")


def memory_peak(devices: List[Any]) -> int:
    """``peak_bytes_in_use`` of the fullest chip (0 where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)
