"""The trace reduction (bench/trace.py): interval arithmetic, gap
attribution, and a small trace recorded on one TPU v5e.

The recorded trace (``data/v5e_tiny.xplane.pb``) is three rounds of four
jitted bf16 matmuls of 1024 x 1024 on one chip, each round after a 2 ms
host sleep inside ``bench.data``, all inside ``bench.window``.
"""
from __future__ import annotations

from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (puts the repository on the path)

from bench import trace as T

DATA = Path(__file__).parent / "data" / "v5e_tiny.xplane.pb"


def test_union_gaps_and_clip():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    busy = T.union(T.clip([(-5, 1), (2, 4), (9, 20)], 0, 10))
    assert busy == [(0, 1), (2, 4), (9, 10)]
    assert T.gaps(busy, 0, 10) == [(1, 2), (4, 9)]
    assert T.gaps([], 0, 10) == [(0, 10)]


def synthetic():
    ops = {"/device:TPU:0": [("%fusion.1 = f32[8] fusion(x)", 10, 30),
                             ("%fusion.1 = f32[8] fusion(x)", 30, 40),
                             ("%all-reduce.2 = f32[8] all-reduce(y)", 60,
                              90)],
           "/device:TPU:1": [("%fusion.1 = f32[8] fusion(x)", 10, 90)]}
    host = [("bench.window", 0, 100), ("bench.round", 0, 50),
            ("bench.data", 45, 70), ("bench.round", 70, 100)]
    return T.Trace(ops, host)


def test_busy_idle_ops_and_gap_names():
    tr = synthetic()
    assert tr.window_s == pytest.approx(100e-9)
    # chip 0 busy 10..40 and 60..90 = 60 ns, chip 1 80 ns: mean 70 ns
    assert tr.busy_s == pytest.approx(70e-9)
    assert tr.op_seconds(T.is_collective) == pytest.approx(15e-9)
    assert tr.op_count(lambda n: T.short(n).startswith("fusion")) == 1.5
    assert tr.top_ops()[0] == ["fusion.1", pytest.approx(55e-9)]
    gaps = dict(tr.idle_gaps())
    # chip 0: 0..10 in a round, 40..60 mid-way in bench.data (the
    # innermost span), 90..100 in a round; chip 1: 0..10, 90..100
    assert gaps["bench.data"] == pytest.approx(10e-9)
    assert gaps["bench.round"] == pytest.approx(20e-9)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        T.Trace({}, [("bench.round", 0, 1)])


def test_recorded_v5e_trace():
    tr = T.Trace.load(str(DATA))
    assert list(tr.ops) == ["/device:TPU:0"]
    assert 0 < tr.busy_s < tr.window_s < 1.0
    top = dict(tr.top_ops())
    assert top and all(t > 0 for t in top.values())
    # four matmul fusions a round, three rounds
    assert tr.op_count(lambda n: T.short(n) == "fusion") == 12
    assert top["fusion"] == max(top.values())
    gaps = dict(tr.idle_gaps())
    # the chip waits while the host sleeps in bench.data and dispatches
    assert set(gaps) == {"bench.data", "bench.round"}
    assert sum(gaps.values()) == pytest.approx(tr.window_s - tr.busy_s)

