"""repro.obs — the unified observability layer (DESIGN.md §12).

One measurement plane for the whole repo: trackers (pluggable sinks in
the ``TRACKERS`` registry) receive structured events, counters, and
nested wall-clock spans from instrumented code, which only ever calls
the free functions here (``span``/``counter``/``event``)
against the process-active tracker. Engines fire :class:`Hooks` at
round/request lifecycle moments; ``report`` renders any finished run
dir's ``summary.json``.
"""
from .context import (counter, event, get_tracker, set_tracker, span,
                      tracing, use_tracker)
from .hooks import HookList, Hooks, TrackerHook, as_hooks
from .report import load_run, render, report
from .tracker import (InMemoryTracker, JsonlTracker, RecordingTracker,
                      StdoutTracker, Tracker, make_tracker)
from .writer import AsyncLineWriter

__all__ = [
    "AsyncLineWriter",
    "HookList",
    "Hooks",
    "InMemoryTracker",
    "JsonlTracker",
    "RecordingTracker",
    "StdoutTracker",
    "Tracker",
    "TrackerHook",
    "as_hooks",
    "counter",
    "event",
    "get_tracker",
    "load_run",
    "make_tracker",
    "render",
    "report",
    "set_tracker",
    "span",
    "tracing",
    "use_tracker",
]
