"""Step/round/request hook registration.

The Trainer and ServeEngine accept a ``hooks`` object and fire it at
the protocol-relevant moments; :class:`TrackerHook` is the stock
implementation that forwards those moments to the active tracker as
events + counters. Everything is a no-op by default so engines can
call hooks unconditionally.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Mapping, Optional

from . import context as obs


class Hooks:
    """No-op base. Subclass and override what you care about."""

    # --- training rounds -------------------------------------------------
    def on_round_start(self, step: int) -> None:
        pass

    def on_round_end(self, step: int, record: Mapping[str, Any]) -> None:
        pass

    # --- serve request lifecycle -----------------------------------------
    def on_admit(self, req: Any) -> None:
        pass

    def on_preempt(self, req: Any) -> None:
        pass

    def on_finish(self, req: Any) -> None:
        pass


class HookList(Hooks):
    """Fans every callback out to a list of hooks, in order."""

    def __init__(self, hooks: Iterable[Hooks]):
        self.hooks = list(hooks)

    def on_round_start(self, step: int) -> None:
        for h in self.hooks:
            h.on_round_start(step)

    def on_round_end(self, step: int, record: Mapping[str, Any]) -> None:
        for h in self.hooks:
            h.on_round_end(step, record)

    def on_admit(self, req: Any) -> None:
        for h in self.hooks:
            h.on_admit(req)

    def on_preempt(self, req: Any) -> None:
        for h in self.hooks:
            h.on_preempt(req)

    def on_finish(self, req: Any) -> None:
        for h in self.hooks:
            h.on_finish(req)


# Round-record fields worth echoing into the event stream; the full
# record already lands in metrics.jsonl, so the event stays compact.
_ROUND_FIELDS = ("loss", "all_echo", "echoed", "bits", "bits_cumulative")


class TrackerHook(Hooks):
    """Forwards engine lifecycle moments to the active tracker."""

    def on_round_start(self, step: int) -> None:
        obs.counter("train.rounds")

    def on_round_end(self, step: int, record: Mapping[str, Any]) -> None:
        if not obs.tracing():
            return
        fields: Dict[str, Any] = {"step": step}
        for k in _ROUND_FIELDS:
            if k in record:
                fields[k] = record[k]
        obs.event("train.round", **fields)

    def on_admit(self, req: Any) -> None:
        obs.counter("serve.admitted")
        obs.event("serve.admit", rid=getattr(req, "rid", None))

    def on_preempt(self, req: Any) -> None:
        obs.counter("serve.preempted")
        obs.event("serve.preempt", rid=getattr(req, "rid", None))

    def on_finish(self, req: Any) -> None:
        obs.counter("serve.finished")
        obs.event("serve.finish", rid=getattr(req, "rid", None),
                  generated=len(getattr(req, "generated", ()) or ()))


def as_hooks(hooks: "Hooks | Iterable[Hooks] | None") -> Hooks:
    """Normalise a hooks argument: None → no-op, iterable → HookList."""
    if hooks is None:
        return Hooks()
    if isinstance(hooks, Hooks):
        return hooks
    return HookList(hooks)
