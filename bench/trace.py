"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

Device planes are ``/device:TPU:<i>``; their ``XLA Ops`` line holds one
event per operation run on that chip. Busy time is the union of those
intervals inside the traced window, the window being the host span
``bench.window`` that the harness opens around the measured loop. An idle
gap is named after the innermost ``bench.*`` host span around its middle:
what the harness was doing while the chip waited. Every number is per
chip and then averaged over the chips.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
HOST_PREFIX = "bench."
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
# ops whose event spans the ops of their body
CONTAINERS = ("while", "conditional", "call")

Interval = Tuple[float, float]


def find(path: str) -> str:
    """The one ``.xplane.pb`` under ``path`` (a file or a trace dir)."""
    if path.endswith(".xplane.pb"):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class Trace:
    """Device ops per chip and the harness's host spans, in ns."""

    def __init__(self, ops: Dict[str, List[Tuple[str, float, float]]],
                 host: List[Tuple[str, float, float]]):
        self.ops = ops                  # chip -> [(name, start, end)]
        self.host = host                # [(name, start, end)] bench.* spans
        wins = [(s, e) for n, s, e in host if n == WINDOW]
        if not wins:
            raise ValueError(f"no {WINDOW!r} host span in the trace")
        self.lo, self.hi = wins[0][0], wins[-1][1]

    @classmethod
    def load(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(find(path))
        ops: Dict[str, List[Tuple[str, float, float]]] = {}
        host: List[Tuple[str, float, float]] = []
        for plane in pd.planes:
            if DEVICE.match(plane.name):
                evs = []
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        evs += [(e.name, e.start_ns, e.end_ns)
                                for e in line.events]
                ops[plane.name] = evs
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host += [(e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(HOST_PREFIX)]
        return cls(ops, host)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def _mean(self, per_chip: Dict[str, float]) -> float:
        return sum(per_chip.values()) / max(len(per_chip), 1)

    def busy(self, chip: str) -> List[Interval]:
        return union(clip([(s, e) for _, s, e in self.ops[chip]],
                          self.lo, self.hi))

    @property
    def busy_s(self) -> float:
        return self._mean({c: sum(e - s for s, e in self.busy(c)) * 1e-9
                           for c in self.ops})

    def op_seconds(self, match) -> float:
        """Mean over chips of the device time of ops whose name matches."""
        return self._mean({c: sum(e - s for n, s, e in
                                  clip_named(evs, self.lo, self.hi)
                                  if match(n)) * 1e-9
                           for c, evs in self.ops.items()})

    def op_count(self, match) -> float:
        return self._mean({c: sum(1 for n, _, _ in
                                  clip_named(evs, self.lo, self.hi)
                                  if match(n))
                           for c, evs in self.ops.items()})

    def top_ops(self, k: int = 10) -> List[List]:
        """The ops that took most device time, by their HLO name; a loop
        or a branch is left out, its body's ops are listed."""
        tot: Dict[str, float] = defaultdict(float)
        for evs in self.ops.values():
            for n, s, e in clip_named(evs, self.lo, self.hi):
                if not short(n).startswith(CONTAINERS):
                    tot[short(n)] += (e - s) * 1e-9 / len(self.ops)
        return [[n, t] for n, t in
                sorted(tot.items(), key=lambda x: -x[1])[:k]]

    def _host_at(self, t: float) -> str:
        best: Optional[Tuple[str, float, float]] = None
        for n, s, e in self.host:
            if n != WINDOW and s <= t <= e and (
                    best is None or e - s < best[2] - best[1]):
                best = (n, s, e)
        return best[0] if best else "bench.between"

    def idle_gaps(self, k: int = 10) -> List[List]:
        tot: Dict[str, float] = defaultdict(float)
        for c in self.ops:
            for s, e in gaps(self.busy(c), self.lo, self.hi):
                tot[self._host_at((s + e) / 2)] += (e - s) * 1e-9 \
                    / len(self.ops)
        return [[n, t] for n, t in
                sorted(tot.items(), key=lambda x: -x[1])[:k]]


def clip_named(evs, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in evs
            if e > lo and s < hi]


def short(name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...), ...`` -> ``fusion.3``: an op
    event carries its whole HLO instruction as its name."""
    return name.split(" = ", 1)[0].lstrip("%")


def is_collective(name: str) -> bool:
    return short(name).startswith(COLLECTIVES)
