"""The program's spans and scopes in a device trace (bench/spans.py): the
XSpace metadata decoder, scope paths, scoped time and idle attribution
on a synthetic trace, and two traces recorded on one TPU v5e.

``data/v5e_tiny.xplane.pb`` is described in test_bench_trace.py.
``data/v5e_scopes_tiny.xplane.pb`` is one episode of the tiny paper cell
(n=12, d=4096, 3 rounds) and two tiny Trainer rounds inside one
``bench.window``, recorded by ``record_scopes_trace.py``.
"""
from __future__ import annotations

from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (puts the repository on the path)

from bench import harness
from bench import spans as S
from repro.obs import scopes

DATA = Path(__file__).parent / "data"
TINY = DATA / "v5e_tiny.xplane.pb"
SCOPES = DATA / "v5e_scopes_tiny.xplane.pb"

# every scope a per-layer metric reads, and the rest of the table
METRIC_SCOPES = {
    "echo_project_ms": scopes.ECHO_PROJECT,
    "slot_server_ms": f"{scopes.PROTOCOL_SLOT}/{scopes.SLOT_SERVER}",
    "slot_overhear_ms": f"{scopes.PROTOCOL_SLOT}/{scopes.SLOT_OVERHEAR}",
    "cgc_ms": scopes.PROTOCOL_AGGREGATE,
    "train_grads_ms": scopes.TRAIN_GRADS,
    "train_optimizer_ms": scopes.TRAIN_OPTIMIZER,
}
PROTOCOL_SCOPES = [scopes.PROTOCOL_SLOT,
                   f"{scopes.PROTOCOL_SLOT}/{scopes.SLOT_DECIDE}",
                   f"{scopes.PROTOCOL_SLOT}/{scopes.SLOT_SERVER}",
                   f"{scopes.PROTOCOL_SLOT}/{scopes.SLOT_OVERHEAR}",
                   scopes.ECHO_PROJECT, scopes.PROTOCOL_GRADS,
                   scopes.PROTOCOL_AGGREGATE, scopes.PROTOCOL_UPDATE]
TRAIN_SCOPES = [scopes.TRAIN_GRADS, scopes.TRAIN_OPTIMIZER]


def test_decoder_reads_the_op_path_of_a_recorded_trace():
    rows = S.event_stats(str(TINY))
    assert list(rows) == ["/device:TPU:0"]
    fusion = [r for r in rows["/device:TPU:0"]
              if str(r.get("name", "")).startswith("%fusion = ")]
    assert fusion and all(r["tf_op"] == "jit(<lambda>)/dot_general:"
                          for r in fusion)
    assert all(isinstance(r["program_id"], int) for r in fusion)
    (source,) = {r["source"] for r in S.event_stats(
        str(TINY), ("source",))["/device:TPU:0"] if "source" in r}
    assert source.endswith(".py:6")       # a stat by its reference


def test_scope_paths():
    assert S.components("jit(f)/transpose(jvp(train.grads))/dot_general:") \
        == ["f", "train.grads", "dot_general"]
    path = "jit(e)/while/body/closed_call/protocol.slot/server/mul:mul"
    assert S.in_scope(path, "protocol.slot/server")
    assert S.in_scope(path, "server")
    assert not S.in_scope(path, "protocol.slot/overhear")
    assert not S.in_scope(path, "server/protocol.slot")
    assert not S.in_scope("jit(e)/protocol.slots/mul", "protocol.slot")
    assert not S.in_scope(None, "server")


def synthetic():
    slot = "jit(e)/while/body/protocol.slot/"
    ops = {"/device:TPU:0": [
        ("%while.1 = while(x)", 10, 40, "jit(e)/while"),
        ("%fusion.1 = f32[8] fusion(x)", 10, 30, slot + "server/mul:"),
        ("%fusion.2 = f32[8] fusion(x)", 30, 40,
         slot + "decide/echo.project/dot_general:"),
        ("%copy.3 = f32[8] copy(x)", 50, 60, None),
        ("%fusion.4 = f32[8] fusion(x)", 90, 130, slot + "server/add:")],
        "/device:TPU:1": [
        ("%fusion.1 = f32[8] fusion(x)", 10, 50, slot + "server/mul:")]}
    host = [("bench.window", 0, 100), ("bench.round", 0, 100),
            ("train.round", 5, 95), ("train.round/step", 5, 20),
            ("train.round/sync", 20, 80), ("other.thread", 60, 70)]
    return S.Spans(ops, host)


def test_scoped_time_and_idle_split():
    sp = synthetic()
    # chip 0: 20 + 10 (clipped at the window's end) ns in server, chip 1 40
    assert sp.scope_seconds("protocol.slot/server") == pytest.approx(35e-9)
    assert sp.scope_seconds("echo.project") == pytest.approx(5e-9)
    assert sp.scope_seconds("protocol.slot") == pytest.approx(40e-9)
    assert sp.scope_seconds("train.grads") is None
    # the while loop spans its body and is left out of scoped time
    assert sp.scoped_busy_s(["protocol.slot"]) == pytest.approx(40e-9)
    assert sp.busy_s == pytest.approx(45e-9)
    # idle, chip 0: 0-10, 40-50, 60-90; chip 1: 0-10, 50-100. Each piece
    # goes to the innermost span named with a prefix; other.thread is not
    split = sp.idle_split(("bench.", "train.round"))
    assert split["bench.round"] == pytest.approx((5 + 5 + 5) / 2 * 1e-9)
    assert split["train.round/step"] == pytest.approx((5 + 5) / 2 * 1e-9)
    assert split["train.round/sync"] == pytest.approx((10 + 20 + 30) / 2
                                                      * 1e-9)
    assert split["train.round"] == pytest.approx((10 + 15) / 2 * 1e-9)
    assert sum(split.values()) == pytest.approx(100e-9 - sp.busy_s)
    assert sp.idle_split()["other.thread"] == pytest.approx(10e-9)
    inner = S.innermost_split([(0, 10), (20, 30)],
                              [("a", 0, 25), ("b", 5, 8)])
    assert inner == {"a": 5 + 2 + 5, "b": 3, None: 5}


def test_readers_leave_out_what_a_trace_lacks(tmp_path):
    """On a trace without the program's scopes and spans every new
    reader returns None, as it does on a program that lacks them."""
    trace = tmp_path / ".trace"
    trace.mkdir()
    (trace / "t.xplane.pb").write_bytes(TINY.read_bytes())
    ctx = {"counters": {"rounds": 3}}
    for name, scope in METRIC_SCOPES.items():
        assert S.scope_ms_per_round(ctx, scope, trace) is None
    reader = harness.metric_reader("trainer_idle_ms")
    reader.__globals__["TRACE"] = trace
    assert reader(ctx) is None
    assert S.scope_ms_per_round(ctx, "x", tmp_path / "none") is None


def test_recorded_scopes_trace(tmp_path):
    assert SCOPES.stat().st_size < 1_000_000
    sp = S.Spans.load(str(SCOPES))
    assert list(sp.ops) == ["/device:TPU:0"]
    for scope in PROTOCOL_SCOPES + TRAIN_SCOPES:
        assert sp.scope_seconds(scope) > 0, scope
    # the named scopes cover the device time of the ops the program
    # names; at this size XLA's own loop control (ops with no op path,
    # a microsecond each per slot) and the gaps inside loops weigh far
    # more than in the cells, where scopes cover over 99 % of busy time
    named = S.union([(s, e) for _, s, e, t in sp.leaves("/device:TPU:0")
                     if t])
    covered = sp.scoped_busy_s(PROTOCOL_SCOPES + TRAIN_SCOPES)
    assert covered >= 0.95 * sum(e - s for s, e in named) * 1e-9
    # the Trainer's spans are on the same clock, nested as in the code
    names = {n for n, _, _ in sp.host}
    assert {"train.round", "train.round/step", "train.round/sync",
            "train.round/record"} <= names
    split = sp.idle_split(("bench.", "train.round"))
    assert split.get("train.round/sync", 0) > 0
    # through the readers, per round of the cell that reads them
    trace = tmp_path / ".trace"
    trace.mkdir()
    (trace / "t.xplane.pb").write_bytes(SCOPES.read_bytes())
    ctx = {"counters": {"rounds": 3}}
    for name, scope in METRIC_SCOPES.items():
        assert S.scope_ms_per_round(ctx, scope, trace) > 0, name
    reader = harness.metric_reader("trainer_idle_ms")
    reader.__globals__["TRACE"] = trace
    assert reader(ctx) > 0
