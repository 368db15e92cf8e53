"""What a driver hands back to the harness, and the window's tracer."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import jax

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = [0]


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == _COMPILE_EVENT:
        _compiles[0] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compiles() -> int:
    """Programs compiled or loaded from the persistent cache so far in this
    process; a window that reads more at its close than at its open
    compiled inside it."""
    return _compiles[0]


@dataclass
class Outcome:
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    numbers: Dict[str, float]          # compared against limits/<cell>.json
    counters: Dict[str, Any] = field(default_factory=dict)
    memory_peak_bytes: int = 0


def start_trace(trace_dir: str) -> None:
    """The profiler with its Python tracer off: it would record every Python
    call of the host path (the pipe's per-token export among them), slowing
    it by orders of magnitude.  Device ops and ``TraceAnnotation`` spans stay."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


class WindowTracer:
    """Traces the measured window, or the part of it a mix asks for
    (``trace: {start, length}``, as shares of the window; no length: to its
    end), under the span ``bench.window``.  Without a directory every call
    does nothing."""

    def __init__(self, trace_dir: Optional[str], seconds: float,
                 start: float = 0.0, length: Optional[float] = None):
        self.dir = trace_dir
        self.start_s = float(start or 0.0) * seconds
        self.length_s = None if length is None else float(length) * seconds
        self.t_open = None
        self.started = self.done = False
        self._span = None
        self.t_begin = None

    def before_window(self) -> None:
        if self.dir and self.start_s == 0.0:
            start_trace(self.dir)

    def open(self, t_open: float) -> None:
        self.t_open = t_open
        if self.dir and self.start_s == 0.0:
            self._begin(t_open)

    def _begin(self, now: float) -> None:
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()
        self.started, self.t_begin = True, now

    def poll(self, now: float) -> None:
        """Start or stop a part-window trace once its time has come."""
        if not self.dir or self.done:
            return
        if not self.started and now >= self.t_open + self.start_s:
            start_trace(self.dir)
            self._begin(now)
        elif self.started and self.length_s is not None \
                and now >= self.t_begin + self.length_s:
            self.close()

    def close(self) -> None:
        if self.started and not self.done:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.done = True
