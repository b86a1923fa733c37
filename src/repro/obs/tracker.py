"""Tracker protocol and the pluggable sinks behind it.

A tracker receives three kinds of signals from instrumented code:

* **events** — structured records (``{"kind": name, **fields}``), e.g.
  one ``comm.round`` event per protocol round with its bit accounting;
* **counters** — monotonically increasing named integers, e.g. per
  kernel-backend dispatch counts;
* **spans** — timed sections with thread-local nesting; nested spans
  produce slash-joined paths (``serve.step/prefill``). Every span is a
  ``jax.profiler.TraceAnnotation`` named by its path, under any tracker,
  so a profiler trace shows it beside the device ops; a recording
  tracker also keeps a per-path ``{count, total_s}`` wall-clock
  aggregate that becomes the per-subsystem breakdown in ``summary.json``.

Sinks live in the ``TRACKERS`` registry: ``noop`` (the default-off
tracker — spans annotate a running profiler trace and record nothing),
``memory`` (tests), ``jsonl`` (one JSON line per event/span via
AsyncLineWriter), ``stdout``. Code under instrumentation never talks to a sink class
directly — it calls the free functions in :mod:`repro.obs.context`,
which dispatch to the active tracker (or to nothing).
"""
from __future__ import annotations

import json
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from ..run.registry import TRACKERS
from .writer import AsyncLineWriter


# Open span paths of this thread, innermost last: nesting is thread-local
# and shared by every tracker, so the profiler annotation carries the same
# slash path whichever tracker (the noop one included) is installed.
_OPEN = threading.local()
_annotation: Optional[Callable[[str], Any]] = None


def _open_paths() -> List[str]:
    stack = getattr(_OPEN, "paths", None)
    if stack is None:
        stack = _OPEN.paths = []
    return stack


def _annotation_type():
    """``jax.profiler.TraceAnnotation``, imported on first use so that
    importing this package (``repro report``) never imports jax."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


class _NoopSpan:
    """Shared do-nothing context manager: a noop-tracker span while no
    profiler trace is running allocates nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP_SPAN = _NoopSpan()


class Tracker:
    """Base tracker; also the noop sink. ``enabled`` lets callers skip
    building event payloads entirely when nothing is listening."""

    enabled = False

    def event(self, kind: str, **fields: Any) -> None:
        pass

    def counter(self, name: str, n: int = 1) -> None:
        pass

    def span(self, name: str):
        if not _annotation_type().is_enabled():
            return _NOOP_SPAN
        return _Span(None, name)

    def snapshot(self) -> Dict[str, Any]:
        return {"counters": {}, "spans": {}}

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


class _Span:
    """One live section: a ``jax.profiler.TraceAnnotation`` named by the
    span's slash path, so that it sits on the profiler's clock beside the
    device ops, and, when ``tracker`` records, a wall-clock time folded
    into its per-path breakdown. Under the noop tracker it times nothing,
    and exists only while a profiler trace runs."""

    __slots__ = ("tracker", "name", "path", "t0", "annotation")

    def __init__(self, tracker: "Optional[RecordingTracker]", name: str):
        self.tracker = tracker
        self.name = name
        self.path = name
        self.t0 = 0.0
        self.annotation = None

    def __enter__(self):
        stack = _open_paths()
        if stack:
            self.path = stack[-1] + "/" + self.name
        stack.append(self.path)
        self.annotation = _annotation_type()(self.path)
        self.annotation.__enter__()
        if self.tracker is not None:
            self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self.tracker is not None:
            dt = time.perf_counter() - self.t0
        self.annotation.__exit__(None, None, None)
        stack = _open_paths()
        if stack and stack[-1] == self.path:
            stack.pop()
        if self.tracker is not None:
            self.tracker._record_span(self.path, dt)
        return False


class RecordingTracker(Tracker):
    """Shared aggregation machinery: counter/span bookkeeping is
    identical across sinks; subclasses only decide where each record
    line goes via ``_emit``."""

    enabled = True

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.counters: Dict[str, int] = {}
        # span path -> [count, total seconds]
        self._spans: Dict[str, List[float]] = {}

    def _emit(self, rec: Dict[str, Any]) -> None:
        pass

    def event(self, kind: str, **fields: Any) -> None:
        self._emit({"kind": kind, **fields})

    def counter(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def span(self, name: str):
        return _Span(self, name)

    def _record_span(self, path: str, dt: float) -> None:
        with self._lock:
            cell = self._spans.get(path)
            if cell is None:
                cell = self._spans[path] = [0, 0.0]
            cell[0] += 1
            cell[1] += dt
        self._emit({"kind": "span", "path": path, "dt_s": dt})

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "counters": dict(self.counters),
                "spans": {
                    path: {"count": int(c), "total_s": t}
                    for path, (c, t) in sorted(self._spans.items())
                },
            }


class InMemoryTracker(RecordingTracker):
    """Keeps every emitted record in ``self.events`` — the test sink."""

    def __init__(self) -> None:
        super().__init__()
        self.events: List[Dict[str, Any]] = []

    def _emit(self, rec: Dict[str, Any]) -> None:
        with self._lock:
            self.events.append(rec)


class JsonlTracker(RecordingTracker):
    """Streams one JSON line per event/span to ``path`` off-thread."""

    def __init__(self, path: str) -> None:
        super().__init__()
        self.path = path
        self._writer = AsyncLineWriter(path)

    def _emit(self, rec: Dict[str, Any]) -> None:
        self._writer.write(json.dumps(rec) + "\n")

    def flush(self) -> None:
        self._writer.flush()

    def close(self) -> None:
        self._writer.close()


class StdoutTracker(RecordingTracker):
    """Prints each record — debugging sink (``--set obs.tracker=stdout``)."""

    def __init__(self, printer: Optional[Callable[[str], None]] = None) -> None:
        super().__init__()
        self._print = printer if printer is not None else print

    def _emit(self, rec: Dict[str, Any]) -> None:
        self._print("[obs] " + json.dumps(rec))


@TRACKERS.register("noop")
def _noop_tracker(**kw: Any) -> Tracker:
    return Tracker()


@TRACKERS.register("memory")
def _memory_tracker(**kw: Any) -> Tracker:
    return InMemoryTracker()


@TRACKERS.register("jsonl")
def _jsonl_tracker(*, path: Optional[str] = None, **kw: Any) -> Tracker:
    if path is None:
        raise ValueError("jsonl tracker requires a path (obs.events_path)")
    return JsonlTracker(path)


@TRACKERS.register("stdout")
def _stdout_tracker(*, printer: Optional[Callable[[str], None]] = None,
                    **kw: Any) -> Tracker:
    return StdoutTracker(printer)


def make_tracker(name: str, *, path: Optional[str] = None,
                 printer: Optional[Callable[[str], None]] = None) -> Tracker:
    """Build the named sink; unknown names raise the registry's
    did-you-mean KeyError."""
    return TRACKERS[name](path=path, printer=printer)
