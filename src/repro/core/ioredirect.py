"""IORedirect (paper section 4): substitute network data pipes for the file
streams an engine's import/export code opens, activated by reserved
filenames, without disturbing any other file the engine touches.

The JVM prototype rewrote bytecode at the discovered call sites.  In Python
the analogous mechanism is a *pipe-aware open*: :func:`pipegen_open` checks
the filename against the reserved template and returns a
``DataPipeOutput``/``DataPipeInput`` (wrapped to the text-file protocol) or
defers to the real ``open``.  Which call sites are *allowed* to redirect is
decided by the capture phase (:mod:`repro.core.capture`): only call sites
observed opening the import/export target during the engine's own unit
tests are registered; every other ``open`` — debug logs, config files —
passes through untouched even when handed a reserved name.
"""

from __future__ import annotations

import builtins
import inspect
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Set, Tuple

from .datapipe import (
    DataPipeInput,
    DataPipeOutput,
    PipeConfig,
    is_reserved,
)

__all__ = [
    "CallSite",
    "CallSiteRegistry",
    "pipegen_open",
    "default_registry",
    "active_pipe_config",
    "set_pipe_config",
    "PipeOpenContext",
]


@dataclass(frozen=True)
class CallSite:
    """A file-open location in engine source (module:function:line)."""

    module: str
    function: str
    lineno: int

    def __str__(self) -> str:
        return f"{self.module}:{self.function}:{self.lineno}"


@dataclass
class CallSiteRegistry:
    """Call sites allowed to redirect, per engine, as discovered by capture.

    ``allow_all`` supports the pre-capture instrumentation run and tests.
    """

    allowed: Set[CallSite] = field(default_factory=set)
    allow_all: bool = False
    observed: Dict[CallSite, Set[str]] = field(default_factory=dict)

    def allow(self, site: CallSite) -> None:
        self.allowed.add(site)

    def permits(self, site: CallSite) -> bool:
        return self.allow_all or site in self.allowed

    def record(self, site: CallSite, filename: str) -> None:
        self.observed.setdefault(site, set()).add(filename)


_default_registry = CallSiteRegistry(allow_all=True)


def default_registry() -> CallSiteRegistry:
    return _default_registry


_config_local = threading.local()


def active_pipe_config() -> PipeConfig:
    return getattr(_config_local, "config", None) or PipeConfig()


def set_pipe_config(config: Optional[PipeConfig]) -> None:
    _config_local.config = config


class PipeOpenContext:
    """``with PipeOpenContext(PipeConfig(...)):`` scopes the pipe behaviour
    (wire format, codec, link simulation) for opens on this thread."""

    def __init__(self, config: PipeConfig):
        self.config = config

    def __enter__(self):
        self._prev = getattr(_config_local, "config", None)
        set_pipe_config(self.config)
        return self

    def __exit__(self, *exc):
        set_pipe_config(self._prev)


def _caller_site(depth: int = 2) -> CallSite:
    fr = inspect.stack()[depth]
    return CallSite(fr.frame.f_globals.get("__name__", "?"), fr.function, fr.lineno)


class _PipeTextWriter:
    """Adapts DataPipeOutput to the text-file protocol engines expect;
    forwards AStrings intact (the FormOpt hand-off, fig. 5 subtyping)."""

    def __init__(self, pipe: DataPipeOutput):
        self.pipe = pipe

    def write(self, s: Any) -> int:
        return self.pipe.write(s)

    def writelines(self, lines) -> None:
        self.pipe.writelines(lines)

    def flush(self) -> None:
        self.pipe.flush()

    def close(self) -> None:
        self.pipe.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class _PipeBytesWriter:
    """Binary write adapter: a shared-binary-format export (e.g. seqfile)
    streams its bytes through the pipe unmodified (section 5's
    shared-binary-format case)."""

    def __init__(self, pipe: DataPipeOutput):
        self.pipe = pipe

    def write(self, b) -> int:
        return self.pipe.write(bytes(b))

    def flush(self) -> None:
        self.pipe.flush()

    def close(self) -> None:
        self.pipe.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class _PipeBytesReader:
    """Binary read adapter over :meth:`DataPipeInput.read_bytes`."""

    def __init__(self, pipe: DataPipeInput):
        self.pipe = pipe

    def read(self, size: int = -1) -> bytes:
        return self.pipe.read_bytes(size)

    def close(self) -> None:
        self.pipe.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def pipegen_open(
    filename: str,
    mode: str = "r",
    registry: Optional[CallSiteRegistry] = None,
    config: Optional[PipeConfig] = None,
    _site_depth: int = 2,
    real_open: Optional[Callable] = None,
    **kw,
):
    """The substituted ``open``.  Reserved name + permitted call site ->
    data pipe; anything else -> the real ``open`` (fig. 4's conditional).

    ``real_open`` is the unspliced ``open`` (the splice must pass it in;
    ``builtins.open`` may *be* the splice while a pipe context is active)."""
    registry = registry or _default_registry
    site = _caller_site(_site_depth)
    registry.record(site, str(filename))
    if is_reserved(str(filename)) and registry.permits(site):
        cfg = config or active_pipe_config()
        binary = "b" in mode
        if any(m in mode for m in ("w", "a", "x")):
            from dataclasses import replace as _replace

            if cfg.partition:
                # N→M shuffle: one writer fanning across all import workers
                if binary:
                    raise ValueError(
                        "partitioned (shuffle) pipes cannot carry opaque "
                        "binary passthrough streams")
                from .fabric import ShuffleWriter

                return _PipeTextWriter(ShuffleWriter(str(filename), config=cfg))
            if binary:
                cfg = _replace(cfg, mode="bytes")
                return _PipeBytesWriter(DataPipeOutput(str(filename), config=cfg))
            return _PipeTextWriter(DataPipeOutput(str(filename), config=cfg))
        pipe = DataPipeInput(str(filename), config=cfg)
        return _PipeBytesReader(pipe) if binary else pipe
    return (real_open or builtins.open)(filename, mode, **kw)
