"""The program's own spans and scopes in a profiler trace (``.xplane.pb``).

``bench/trace.py`` reduces the window to device ops and the harness's
``bench.*`` host spans. This module reads the same file a second time
for what the program itself puts there:

- host spans: every annotation on a host plane, all names and not only
  ``bench.*``. ``repro.obs`` spans are annotations named by their slash
  path (``train.round/sync``); read with ``jax.profiler.ProfileData``.
- device scopes: a ``jax.named_scope`` reaches each op's ``op_name``,
  which the TPU profiler keeps as the op's ``tf_op`` stat
  (``jit(episode)/while/body/closed_call/protocol.slot/server/...:``).
  ``ProfileData`` does not expose event metadata, so a small decoder of
  the protobuf wire format reads it from the XSpace message (below).

The scoped time of a scope is the device time of the leaf ops whose
``tf_op`` path holds it as a component (a slash scope such as
``protocol.slot/server`` as consecutive components), clipped to
``bench.window``, loops and branches left out as ``bench/trace.py``
leaves them out, and averaged over chips. A transform's wrapper around a
component (``transpose(jvp(train.grads))``) is taken off before the
comparison.

Fields of ``tensorflow/tsl/profiler/protobuf/xplane.proto`` used here:
``XSpace.planes`` (1); ``XPlane.name`` (2), ``.event_metadata`` (4) and
``.stat_metadata`` (5), maps whose entries hold the key in field 1 and
the value in field 2; ``XEventMetadata.name`` (2) and ``.stats`` (5);
``XStatMetadata.name`` (2); ``XStat.metadata_id`` (1) and its value,
``uint64_value`` (3), ``int64_value`` (4), ``str_value`` (5) or
``ref_value`` (7, the id of a stat metadata whose name is the string).
``XPlane.lines`` (3) are skipped without being decoded.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from bench.trace import (CONTAINERS, DEVICE, OPS_LINE, WINDOW, Interval,
                         clip, find, gaps, short, union)

MODULES_LINE = "XLA Modules"
# a module event is named ``jit_episode(12463548226743970204)``
PROGRAM_ID = re.compile(r"\((\d+)\)$")
# a transform's wrapper around one name-stack component
WRAPPED = re.compile(r"^[\w.-]+\((.*)\)$")

Op = Tuple[str, float, float, Optional[str]]     # name, start, end, tf_op


# ---------------------------------------------------------------------------
# The protobuf wire format, as far as the XSpace metadata needs it
# ---------------------------------------------------------------------------


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf: memoryview) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, the
    bytes (a view, not a copy) of a length-delimited field, fixed-width
    fields as raw bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} in field {num}")
        yield num, value


def _map_values(entries: List[memoryview]) -> Iterator[memoryview]:
    for entry in entries:
        for num, value in fields(entry):
            if num == 2:
                yield value


def _stat(stat: memoryview, names: Dict[int, str]) -> Tuple[str, object]:
    """(stat name, value) of one XStat; a string is str_value or the name
    of the stat metadata that ref_value points to."""
    key, value = None, None
    for num, v in fields(stat):
        if num == 1:
            key = v
        elif num == 5:
            value = bytes(v).decode("utf-8", "replace")
        elif num == 7:
            value = names.get(v)
        elif num in (3, 4):
            value = v
    return names.get(key, ""), value


def event_stats(path: str, wanted: Sequence[str] = ("tf_op", "program_id")
                ) -> Dict[str, List[Dict[str, object]]]:
    """Per device plane, the ``wanted`` stats of each event metadata, with
    its ``name`` (for an XLA op, the whole HLO instruction, as
    ``ProfileData`` names its events)."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out: Dict[str, List[Dict[str, object]]] = {}
    for num, plane in fields(space):
        if num != 1:
            continue
        name, events, stats = "", [], []
        for pnum, value in fields(plane):
            if pnum == 2:
                name = bytes(value).decode()
            elif pnum == 4:
                events.append(value)
            elif pnum == 5:
                stats.append(value)
        if not DEVICE.match(name):
            continue
        stat_names: Dict[int, str] = {}
        for meta in _map_values(stats):
            got = dict(fields(meta))
            stat_names[got.get(1, 0)] = bytes(got.get(2, b"")).decode()
        rows = []
        for meta in _map_values(events):
            row: Dict[str, object] = {}
            for enum, value in fields(meta):
                if enum == 2:
                    row["name"] = bytes(value).decode("utf-8", "replace")
                elif enum == 5:
                    key, v = _stat(value, stat_names)
                    if key in wanted:
                        row[key] = v
            rows.append(row)
        out[name] = rows
    return out


# ---------------------------------------------------------------------------
# Scope paths
# ---------------------------------------------------------------------------


def components(tf_op: str) -> List[str]:
    """``jit(f)/transpose(jvp(train.grads))/dot_general:`` ->
    ``['f', 'train.grads', 'dot_general']``: the op's name-stack path,
    its ``:type`` suffix and each transform wrapper taken off."""
    path = tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op
    out = []
    for comp in path.split("/"):
        m = WRAPPED.match(comp)
        while m:
            comp = m.group(1)
            m = WRAPPED.match(comp)
        out.append(comp)
    return out


def in_scope(tf_op: Optional[str], scope: str) -> bool:
    """Whether ``scope`` (``a`` or ``a/b``) is a run of components of the
    op's path."""
    if not tf_op:
        return False
    want = scope.split("/")
    comps = components(tf_op)
    k = len(want)
    return any(comps[i:i + k] == want for i in range(len(comps) - k + 1))


# ---------------------------------------------------------------------------
# The trace
# ---------------------------------------------------------------------------


def innermost_split(intervals: Sequence[Interval],
                    spans: Sequence[Tuple[str, float, float]]
                    ) -> Dict[Optional[str], float]:
    """Length of ``intervals`` (sorted, disjoint) inside each span, each
    piece given to the innermost (shortest) span around it; ``None`` for
    what lies in no span."""
    ev = []
    for k, (_, s, e) in enumerate(spans):
        ev += [(s, 1, k), (e, -1, k)]
    for s, e in intervals:
        ev += [(s, 2, -1), (e, -2, -1)]
    ev.sort(key=lambda x: x[0])
    out: Dict[Optional[str], float] = defaultdict(float)
    active: Dict[int, Tuple[float, str]] = {}
    inside, prev = 0, None
    for t, kind, k in ev:
        if inside and prev is not None and t > prev:
            inner = min(active.values())[1] if active else None
            out[inner] += t - prev
        prev = t
        if kind == 1:
            active[k] = (spans[k][2] - spans[k][1], spans[k][0])
        elif kind == -1:
            active.pop(k, None)
        else:
            inside += 1 if kind == 2 else -1
    return dict(out)


class Spans:
    """Device ops with their scope paths, and every host span, in ns."""

    def __init__(self, ops: Dict[str, List[Op]],
                 host: List[Tuple[str, float, float]]):
        self.ops = ops                  # chip -> [(name, start, end, tf_op)]
        self.host = host                # [(name, start, end)], all names
        wins = [(s, e) for n, s, e in host if n == WINDOW]
        if not wins:
            raise ValueError(f"no {WINDOW!r} host span in the trace")
        self.lo, self.hi = wins[0][0], wins[-1][1]
        self._matches: Dict[Tuple[Optional[str], str], bool] = {}

    @classmethod
    def load(cls, path: str) -> "Spans":
        from jax.profiler import ProfileData

        file = find(path)
        metas = event_stats(file)
        pd = ProfileData.from_file(file)
        ops: Dict[str, List[Op]] = {}
        host: List[Tuple[str, float, float]] = []
        for plane in pd.planes:
            if DEVICE.match(plane.name):
                ops[plane.name] = _scoped_ops(plane, metas.get(plane.name,
                                                               []))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host += [(e.name, e.start_ns, e.end_ns)
                             for e in line.events]
        return cls(ops, host)

    def _mean(self, per_chip: Dict[str, float]) -> float:
        return sum(per_chip.values()) / max(len(per_chip), 1)

    def leaves(self, chip: str) -> List[Op]:
        """The chip's ops in the window, clipped to it, loops and branches
        left out."""
        return [(n, max(s, self.lo), min(e, self.hi), t)
                for n, s, e, t in self.ops[chip]
                if e > self.lo and s < self.hi
                and not short(n).startswith(CONTAINERS)]

    def _in(self, tf_op: Optional[str], scope: str) -> bool:
        """``in_scope``, once per distinct path (a trace repeats a few
        hundred paths over its ops)."""
        key = (tf_op, scope)
        if key not in self._matches:
            self._matches[key] = in_scope(tf_op, scope)
        return self._matches[key]

    def scope_seconds(self, scope: str) -> Optional[float]:
        """Mean over chips of the device time of the leaf ops in
        ``scope``; None when no op of the trace carries it."""
        if not any(self._in(t, scope) for evs in self.ops.values()
                   for _, _, _, t in evs):
            return None
        return self._mean({c: sum(e - s for _, s, e, t in self.leaves(c)
                                  if self._in(t, scope)) * 1e-9
                           for c in self.ops})

    def busy(self, chip: str) -> List[Interval]:
        return union(clip([(s, e) for _, s, e, _ in self.ops[chip]],
                          self.lo, self.hi))

    @property
    def busy_s(self) -> float:
        return self._mean({c: sum(e - s for s, e in self.busy(c)) * 1e-9
                           for c in self.ops})

    def scoped_busy_s(self, scopes: Sequence[str]) -> float:
        """Mean over chips of the device time covered by leaf ops in any
        of ``scopes``."""
        return self._mean({c: sum(e - s for s, e in union(
            [(s, e) for _, s, e, t in self.leaves(c)
             if any(self._in(t, sc) for sc in scopes)])) * 1e-9
            for c in self.ops})

    def idle_split(self, prefixes: Sequence[str] = ("",)
                   ) -> Dict[Optional[str], float]:
        """Mean over chips of the device's idle time in the window, given
        to the innermost host span around it among those whose name starts
        with one of ``prefixes`` (None: in none of them), in seconds."""
        spans = [(n, s, e) for n, s, e in self.host
                 if n != WINDOW and n.startswith(tuple(prefixes))]
        tot: Dict[Optional[str], float] = defaultdict(float)
        for c in self.ops:
            idle = gaps(self.busy(c), self.lo, self.hi)
            for name, t in innermost_split(idle, spans).items():
                tot[name] += t * 1e-9 / len(self.ops)
        return dict(tot)


def _scoped_ops(plane, metas: List[Dict[str, object]]) -> List[Op]:
    """The plane's XLA ops, each with the ``tf_op`` of its metadata. One
    HLO text can occur in two programs, so an op is looked up under the
    program of the ``XLA Modules`` event around it, and by its text alone
    where that text has one ``tf_op`` in the whole plane."""
    by_prog: Dict[Tuple[object, str], str] = {}
    by_name: Dict[str, set] = defaultdict(set)
    for m in metas:
        if m.get("tf_op") and "name" in m:
            by_prog.setdefault((m.get("program_id"), m["name"]), m["tf_op"])
            by_name[m["name"]].add(m["tf_op"])
    modules, evs = [], []
    for line in plane.lines:
        if line.name == MODULES_LINE:
            for e in line.events:
                got = PROGRAM_ID.search(e.name)
                if got:
                    modules.append((e.start_ns, e.end_ns, int(got.group(1))))
        elif line.name == OPS_LINE:
            evs += [(e.name, e.start_ns, e.end_ns) for e in line.events]
    modules.sort()
    starts = [s for s, _, _ in modules]
    out: List[Op] = []
    for name, s, e in evs:
        tf_op = None
        k = bisect.bisect_right(starts, s) - 1
        if k >= 0 and modules[k][1] >= s:
            tf_op = by_prog.get((modules[k][2], name))
        if tf_op is None and len(by_name.get(name, ())) == 1:
            tf_op = next(iter(by_name[name]))
        out.append((name, s, e, tf_op))
    return out


# ---------------------------------------------------------------------------
# What the metric readers share
# ---------------------------------------------------------------------------


def of_run(ctx, trace_dir: Path) -> Optional[Spans]:
    """The run's trace, read once for all readers of one run (kept in
    ``ctx``); None when the run wrote none."""
    key = ("program_spans", str(trace_dir))
    if key not in ctx:
        try:
            ctx[key] = Spans.load(str(trace_dir))
        except (FileNotFoundError, ValueError):
            ctx[key] = None
    return ctx[key]


def scope_ms_per_round(ctx, scope: str, trace_dir: Path) -> Optional[float]:
    """Device ms a round spends in ``scope``; None when nothing carries
    it or no round completed."""
    rounds = ctx["counters"].get("rounds")
    sp = of_run(ctx, trace_dir)
    if sp is None or not rounds:
        return None
    seconds = sp.scope_seconds(scope)
    return None if seconds is None else 1e3 * seconds / rounds
