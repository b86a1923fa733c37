"""The active tracker and the free functions instrumented code calls.

Instrumentation sites (``Trainer.run_round``, the serve step loop, the
ledger, kernel dispatch, the checkpoint writer thread) never hold a
tracker reference — they call :func:`span` / :func:`counter` /
:func:`event` here, which dispatch to whatever tracker is currently
installed. The default is the shared noop tracker: counters and events
cost one attribute check, and a span allocates nothing unless a
profiler trace is running, when it opens an annotation named by its
path (``repro.obs.tracker._Span``), so a trace names the program's
sections whatever tracker is installed.

The active tracker is process-global rather than thread-local on
purpose: background threads (checkpoint writer, metrics writer) must
land their spans in the same breakdown as the driver loop. Span
*nesting* stays thread-local, so cross-thread spans never corrupt each
other's paths.
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Any

from .tracker import Tracker

_NOOP = Tracker()
_ACTIVE: Tracker = _NOOP


def get_tracker() -> Tracker:
    return _ACTIVE


def set_tracker(tracker: "Tracker | None") -> Tracker:
    """Install ``tracker`` (None → noop); returns the previous one."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = tracker if tracker is not None else _NOOP
    return prev


@contextmanager
def use_tracker(tracker: "Tracker | None"):
    """Scope ``tracker`` as the active sink; restores on exit."""
    prev = set_tracker(tracker)
    try:
        yield tracker
    finally:
        set_tracker(prev)


def span(name: str):
    """Timed section under the active tracker, and a profiler annotation
    under any. Nesting slash-joins the names: ``with span("serve.step"):
    with span("prefill")`` records and annotates ``serve.step/prefill``."""
    return _ACTIVE.span(name)


def counter(name: str, n: int = 1) -> None:
    if _ACTIVE.enabled:
        _ACTIVE.counter(name, n)


def event(kind: str, **fields: Any) -> None:
    if _ACTIVE.enabled:
        _ACTIVE.event(kind, **fields)


def tracing() -> bool:
    """True when a real (non-noop) tracker is installed — lets call
    sites skip building expensive event payloads."""
    return _ACTIVE.enabled
