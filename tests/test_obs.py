"""The observability layer (repro.obs, DESIGN.md §12): tracker sinks,
span nesting, the async line writer's error contract, run summaries and
the ``python -m repro report`` CLI.

Single-worker paths run in-process; the echo_dp strategy needs >1
data-parallel workers, so that leg runs in a subprocess with 8 forced
host devices (the test_dist.py pattern).
"""
import json
import os
import subprocess
import sys
import textwrap
import threading

import pytest

from repro import obs
from repro.obs.writer import AsyncLineWriter
from repro.run import ObsSpec, TRACKERS


# ---------------------------------------------------------------------------
# AsyncLineWriter: ordering, error surfacing, atexit flush
# ---------------------------------------------------------------------------


def test_async_line_writer_roundtrip(tmp_path):
    path = tmp_path / "out.jsonl"
    w = AsyncLineWriter(str(path))
    for i in range(100):
        w.write(f"line {i}\n")
    assert w.flush()
    assert path.read_text().splitlines()[0] == "line 0"
    w.close()
    assert path.read_text().splitlines() == [f"line {i}" for i in range(100)]
    w.close()                                  # idempotent


def test_async_line_writer_surfaces_background_error(tmp_path):
    w = AsyncLineWriter(str(tmp_path / "x.jsonl"))
    w._fh.close()                              # sabotage the sink
    w.write("doomed\n")
    with pytest.raises(RuntimeError, match="background write"):
        w.flush()
    w.close(reraise=False)                     # drained error; clean close


def test_async_line_writer_close_reraises(tmp_path):
    w = AsyncLineWriter(str(tmp_path / "x.jsonl"))
    w._fh.close()
    w.write("doomed\n")
    with pytest.raises(RuntimeError, match="background write"):
        w.close()
    w.close()                                  # already closed: no-op


def test_async_line_writer_write_after_close_raises(tmp_path):
    w = AsyncLineWriter(str(tmp_path / "x.jsonl"))
    w.close()
    with pytest.raises(RuntimeError, match="closed"):
        w.write("late\n")


def test_async_line_writer_atexit_flushes_tail(tmp_path):
    """A process that exits without close() still lands its records —
    the atexit sweep drains every live writer."""
    path = tmp_path / "tail.jsonl"
    code = textwrap.dedent(f"""
        from repro.obs.writer import AsyncLineWriter
        w = AsyncLineWriter({str(path)!r})
        for i in range(50):
            w.write(f"rec {{i}}\\n")
        # no close(), no flush(): atexit must land these
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr
    assert path.read_text().splitlines() == [f"rec {i}" for i in range(50)]


def test_metrics_sink_surfaces_writer_error(tmp_path):
    """MetricsSink honours the AsyncCheckpointWriter error contract:
    background write failures re-raise on flush()/close()."""
    from repro.launch.engine import MetricsSink

    sink = MetricsSink(str(tmp_path / "metrics.jsonl"), log_every=100,
                       printer=lambda s: None)
    sink.emit({"step": 0, "loss": 1.0})
    sink._writer._fh.close()                   # sabotage
    sink.emit({"step": 1, "loss": 0.5})
    with pytest.raises(RuntimeError, match="background write"):
        sink.flush()
    sink.close()                               # error already consumed


def test_metrics_sink_jsonl_shape(tmp_path):
    from repro.launch.engine import MetricsSink

    path = tmp_path / "metrics.jsonl"
    sink = MetricsSink(str(path), log_every=100, printer=lambda s: None)
    records = [{"step": i, "loss": 1.0 / (i + 1)} for i in range(5)]
    for rec in records:
        sink.emit(rec)
    sink.close()
    assert [json.loads(l) for l in path.read_text().splitlines()] == records


# ---------------------------------------------------------------------------
# Tracker sinks + the context API
# ---------------------------------------------------------------------------


def test_tracker_registry_and_make_tracker(tmp_path, capsys):
    assert {"noop", "memory", "jsonl", "stdout"} <= set(TRACKERS)
    assert obs.make_tracker("noop").enabled is False
    with pytest.raises(KeyError, match="noop"):
        obs.make_tracker("nopo")               # did-you-mean
    with pytest.raises(ValueError, match="path"):
        obs.make_tracker("jsonl")

    printed = []
    t = obs.make_tracker("stdout", printer=printed.append)
    t.event("hello", x=1)
    assert printed == ['[obs] {"kind": "hello", "x": 1}']

    path = tmp_path / "events.jsonl"
    t = obs.make_tracker("jsonl", path=str(path))
    t.event("e", n=2)
    with t.span("work"):
        pass
    t.counter("hits", 3)
    t.close()
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    assert recs[0] == {"kind": "e", "n": 2}
    assert recs[1]["kind"] == "span" and recs[1]["path"] == "work"
    assert t.snapshot()["counters"] == {"hits": 3}


def test_context_noop_until_tracker_set(tmp_path):
    import jax

    assert not obs.tracing()
    assert obs.span("x") is obs.tracker._NOOP_SPAN   # no trace: free
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("x") as sp:              # annotates, records nothing
            assert sp.tracker is None and sp.path == "x"
    obs.counter("x")                           # all silently dropped
    obs.event("x", a=1)
    t = obs.InMemoryTracker()
    with obs.use_tracker(t):
        assert obs.tracing()
        obs.counter("x", 2)
    assert not obs.tracing()                   # restored on exit
    assert t.counters == {"x": 2}


def test_span_nesting_builds_slash_paths():
    t = obs.InMemoryTracker()
    with obs.use_tracker(t):
        with obs.span("train.round"):
            with obs.span("optimistic"):
                pass
            with obs.span("fallback"):
                pass
        with obs.span("train.round"):
            with obs.span("optimistic"):
                pass
    spans = t.snapshot()["spans"]
    assert set(spans) == {"train.round", "train.round/optimistic",
                          "train.round/fallback"}
    assert spans["train.round"]["count"] == 2
    assert spans["train.round/optimistic"]["count"] == 2
    assert spans["train.round/fallback"]["count"] == 1
    # exit order: inner spans close (and record) before their parent
    paths = [e["path"] for e in t.events if e["kind"] == "span"]
    assert paths[0] == "train.round/optimistic"
    assert paths.index("train.round/fallback") \
        < paths.index("train.round")


def test_span_nesting_is_thread_local():
    """A span opened on another thread is a root span there — it never
    inherits this thread's open path (the checkpoint-writer case)."""
    t = obs.InMemoryTracker()
    with obs.use_tracker(t):
        with obs.span("main.outer"):
            th = threading.Thread(
                target=lambda: obs.span("worker.write").__enter__()
                .__exit__(None, None, None))
            th.start()
            th.join()
    assert set(t.snapshot()["spans"]) == {"main.outer", "worker.write"}


def test_noop_span_is_cheap():
    """Under the noop tracker, with no profiler trace running, a span
    costs a check of the profiler's state: well under a microsecond."""
    import timeit

    def once():
        with obs.span("train.round"):
            with obs.span("sync"):
                pass

    once()
    per_span = min(timeit.repeat(once, number=2000, repeat=5)) / 4000
    assert per_span < 10e-6


def _host_events(trace_dir):
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return [(e.name, e.start_ns, e.end_ns)
            for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:")
            for line in p.lines for e in line.events]


def _tiny_trainer(**settings):
    import jax.numpy as jnp

    from repro.launch.engine import (ReplicatedStrategy, Trainer,
                                     TrainerConfig, TrainSettings)
    from repro.optim import sgd

    def loss(values, batch):
        w = values["w"]
        return (0.5 * jnp.sum((w - 1.0) ** 2)
                + w @ jnp.mean(batch["eps"], 0)), {}

    tr = Trainer(ReplicatedStrategy(loss_fn=loss), None, sgd(0.1),
                 TrainSettings(aggregator="mean", **settings), None, 4,
                 TrainerConfig(), printer=lambda s: None)
    state = tr.init_state({"w": jnp.zeros(16)})
    return tr, state, {"eps": jnp.ones((4, 16))}


def test_round_spans_are_profiler_annotations(tmp_path):
    """With the noop tracker a Trainer round still puts its spans on the
    profiler's clock, named by slash path and nested as in the code: the
    dispatch, the wait on the device, the bookkeeping."""
    import jax

    tr, state, batch = _tiny_trainer()
    state, _ = tr.run_round(state, batch)      # compiles outside the trace
    assert not obs.tracing()
    with jax.profiler.trace(str(tmp_path)):
        state, _ = tr.run_round(state, batch)
    got = {n: (s, e) for n, s, e in _host_events(str(tmp_path))
           if n.startswith("train.")}
    assert set(got) == {"train.round", "train.round/step",
                        "train.round/sync", "train.round/record"}
    lo, hi = got["train.round"]
    order = [got[f"train.round/{c}"] for c in ("step", "sync", "record")]
    assert lo <= order[0][0] and order[-1][1] <= hi
    assert all(a[1] <= b[0] for a, b in zip(order, order[1:]))


def test_scopes_reach_op_names():
    """Each device scope lands in the op_name metadata of the lowered
    protocol round, run_training's scan, the Trainer step and the echo
    basis Gram: what a device trace reports as each op's tf_op."""
    import re

    import jax
    import jax.numpy as jnp

    from repro.core import byzantine, costfns
    from repro.core.protocol import echo_cgc_round, run_training
    from repro.core.types import ProtocolConfig
    from repro.dist.echo_dp import basis_gram
    from repro.obs import scopes as sc

    def op_names(lowered):
        return set(re.findall(r'op_name="([^"]*)"',
                              lowered.as_text(dialect="hlo",
                                              debug_info=True)))

    def has(names, scope):
        return any(f"/{scope}/" in f"/{n}/" for n in names)

    n, d = 5, 32
    cfg = ProtocolConfig(n=n, f=1, r=0.5, eta=0.1)
    key = jax.random.PRNGKey(0)
    grads = jax.random.normal(key, (n, d))
    byz = jnp.arange(n) < 1
    plan = byzantine.ATTACKS["sign_flip"](key, grads, byz, jnp.zeros(d),
                                          grads.mean(0))
    names = op_names(echo_cgc_round.lower(cfg, jnp.zeros(d), grads, byz,
                                          plan))
    for scope in (f"{sc.PROTOCOL_SLOT}/{sc.SLOT_DECIDE}/{sc.ECHO_PROJECT}",
                  f"{sc.PROTOCOL_SLOT}/{sc.SLOT_SERVER}",
                  f"{sc.PROTOCOL_SLOT}/{sc.SLOT_OVERHEAR}",
                  sc.PROTOCOL_AGGREGATE, sc.PROTOCOL_UPDATE):
        assert has(names, scope), scope

    cost = costfns.quadratic(key, d=d, mu=0.5, L=1.0, sigma=0.1)
    names = op_names(jax.jit(lambda k, w: run_training(
        cfg, cost, byzantine.ATTACKS["sign_flip"], byz, k, w,
        rounds=2)["w_final"]).lower(key, jnp.ones(d)))
    assert has(names, sc.PROTOCOL_GRADS)
    assert has(names, f"{sc.PROTOCOL_SLOT}/{sc.SLOT_SERVER}")

    tr, state, batch = _tiny_trainer(clip_norm=1.0)
    names = op_names(tr.step_fn.lower(state.values, state.opt_state, batch,
                                      jnp.asarray(0)))
    for scope in (sc.TRAIN_GRADS, sc.TRAIN_AGGREGATE, sc.TRAIN_OPTIMIZER):
        assert has(names, scope), scope

    basis = [{"w": jnp.ones(8) * i} for i in range(3)]
    assert has(op_names(jax.jit(basis_gram).lower(basis)),
               sc.ECHO_BASIS_GRAM)


def test_jitted_run_training_counts_no_rounds():
    """Under an outer jit run_training's body runs once per trace: its
    protocol.rounds span and rounds counter run only on the eager call,
    so they neither time tracing nor count traces."""
    import jax
    import jax.numpy as jnp

    from repro.core import byzantine, costfns
    from repro.core.protocol import run_training
    from repro.core.types import ProtocolConfig

    n, d = 4, 16
    cfg = ProtocolConfig(n=n, f=1, r=0.5, eta=0.1)
    key = jax.random.PRNGKey(1)
    cost = costfns.quadratic(key, d=d, mu=0.5, L=1.0, sigma=0.1)
    byz = jnp.arange(n) < 1

    def run(k, w0):
        return run_training(cfg, cost, byzantine.ATTACKS["sign_flip"], byz,
                            k, w0, rounds=2)

    t = obs.InMemoryTracker()
    with obs.use_tracker(t):
        jitted = jax.jit(run)(key, jnp.ones(d))
        assert "protocol.rounds_simulated" not in t.counters
        assert "protocol.rounds" not in t.snapshot()["spans"]
        eager = run(key, jnp.ones(d))
    assert t.counters["protocol.rounds_simulated"] == 2
    assert t.snapshot()["spans"]["protocol.rounds"]["count"] == 1
    assert jnp.allclose(jitted["w_final"], eager["w_final"])


# ---------------------------------------------------------------------------
# Facade runs: summary.json + span/counter totals
# ---------------------------------------------------------------------------


def _quad_cfg(tmp_path, tracker="memory", steps=3):
    from repro.run import (DataSpec, MeshSpec, RunConfig, ScenarioSpec,
                           TrainSpec)
    return RunConfig(
        name="obs-quad",
        model=None,
        mesh=MeshSpec(devices=0),
        scenario=ScenarioSpec(
            aggregator="mean", f=0,
            data=DataSpec(source="quadratic", dim=16, mu=0.5, L=1.0,
                          noise=1e-3)),
        train=TrainSpec(strategy="replicated", steps=steps, batch=4,
                        optimizer="sgd", lr=0.1, log_every=100),
        obs=ObsSpec(tracker=tracker),
        runs_root=str(tmp_path / "runs"))


def test_train_run_writes_summary_with_span_breakdown(tmp_path):
    from repro.run import facade

    result = facade.train(_quad_cfg(tmp_path, tracker="memory"))
    data = json.load(open(os.path.join(result.run_dir, "summary.json")))
    assert data["kind"] == "train"
    assert data["summary"]["rounds"] == 3
    snap = data["obs"]
    assert snap["counters"]["train.rounds"] == 3
    assert snap["spans"]["train.round"]["count"] == 3
    for child in ("step", "sync", "record"):
        assert snap["spans"][f"train.round/{child}"]["count"] == 3
    assert snap["spans"]["train.data"]["count"] >= 3


def test_train_run_jsonl_tracker_streams_events(tmp_path):
    from repro.run import facade

    result = facade.train(_quad_cfg(tmp_path, tracker="jsonl"))
    events_path = os.path.join(result.run_dir, "events.jsonl")
    recs = [json.loads(l) for l in open(events_path).read().splitlines()]
    span_paths = {r["path"] for r in recs if r["kind"] == "span"}
    assert "train.round" in span_paths and "train.round/step" in span_paths
    # report renders the finished dir
    text = obs.report(result.run_dir, printer=lambda s: None)
    assert "== repro report: train 'obs-quad'" in text
    assert "span breakdown" in text and "train.round" in text


def test_echo_dp_three_rounds_span_and_counter_totals(tmp_path):
    """The issue's acceptance check: a seeded 3-round echo_dp quadratic
    run records the optimistic/fallback span taxonomy and per-round
    comm counters (in-memory tracker, snapshot via summary.json)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import json
        from repro.run import (DataSpec, MeshSpec, ObsSpec, RunConfig,
                               ScenarioSpec, TrainSpec, facade)
        from repro.obs import report

        cfg = RunConfig(
            name="obs-echo", model=None, mesh=MeshSpec(devices=8),
            scenario=ScenarioSpec(aggregator="cgc", f=1, echo_k=4,
                                  echo_r=0.9,
                                  data=DataSpec(source="quadratic",
                                                dim=64, noise=1e-3)),
            train=TrainSpec(strategy="echo_dp", steps=3, batch=8,
                            optimizer="sgd", lr=0.02, log_every=100),
            obs=ObsSpec(tracker="memory"),
            runs_root=os.environ["OBS_RUNS_ROOT"])
        result = facade.train(cfg)
        data = json.load(open(os.path.join(result.run_dir,
                                           "summary.json")))
        snap = data["obs"]
        assert snap["counters"]["train.rounds"] == 3
        assert snap["counters"]["comm.rounds"] == 3
        assert snap["counters"]["comm.bits_sent"] \\
            == data["summary"]["bits_sent"]
        spans = snap["spans"]
        assert spans["train.round"]["count"] == 3
        assert "train.round/optimistic" in spans
        assert data["summary"]["echo_rounds"] \\
            == snap["counters"].get("comm.echo_rounds", 0)
        text = report(result.run_dir, printer=lambda s: None)
        assert "echo rounds" in text and "optimistic" in text
        print("OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["OBS_RUNS_ROOT"] = str(tmp_path / "runs")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "OK" in r.stdout


# ---------------------------------------------------------------------------
# repro report: golden rendering + CLI
# ---------------------------------------------------------------------------

_GOLDEN_SUMMARY = {
    "kind": "train",
    "summary": {"rounds": 2, "wall_s": 4.0, "first_loss": 1.0,
                "final_loss": 0.5, "echo_rounds": 1, "echo_rate": 0.5,
                "bits_sent": 1000.0, "bits_baseline": 4000.0,
                "bits_saving": 0.75},
    "obs": {"counters": {"train.rounds": 2, "comm.rounds": 2},
            "spans": {"train.round": {"count": 2, "total_s": 3.0},
                      "train.round/step": {"count": 2, "total_s": 1.0},
                      "train.round/sync": {"count": 2, "total_s": 1.5},
                      "train.round/record": {"count": 2, "total_s": 0.5},
                      "train.data": {"count": 2, "total_s": 1.0}}},
}

_GOLDEN_TEXT = """\
== repro report: train 'golden' ==
  rounds        2  (wall 4.0s)
  rounds/s      0.50
  loss          1 -> 0.5
  echo rounds   1/2 (50.0%)
  bits sent     1000 vs baseline 4000 (75.0% saved)
-- span breakdown (share of root spans) --
  train.data    25.0%  total     1.00s  n=2      mean 500.00ms
  train.round   75.0%  total     3.00s  n=2      mean 1.50s
    record      12.5%  total  500.00ms  n=2      mean 250.00ms
    step        25.0%  total     1.00s  n=2      mean 500.00ms
    sync        37.5%  total     1.50s  n=2      mean 750.00ms
-- counters --
  comm.rounds   2
  train.rounds  2"""


def _golden_run_dir(tmp_path):
    with open(tmp_path / "summary.json", "w") as fh:
        json.dump(_GOLDEN_SUMMARY, fh)
    with open(tmp_path / "config.json", "w") as fh:
        json.dump({"name": "golden"}, fh)
    return str(tmp_path)


def test_report_golden(tmp_path):
    run_dir = _golden_run_dir(tmp_path)
    assert obs.render(obs.load_run(run_dir)) == _GOLDEN_TEXT


def test_report_cli(tmp_path, capsys):
    from repro.__main__ import main

    run_dir = _golden_run_dir(tmp_path)
    assert main(["report", run_dir]) == 0
    out = capsys.readouterr().out
    assert "== repro report: train 'golden'" in out
    assert "75.0% saved" in out and "span breakdown" in out


def test_report_missing_summary_is_friendly(tmp_path):
    from repro.__main__ import main

    with pytest.raises(FileNotFoundError, match="summary.json"):
        obs.load_run(str(tmp_path))
    with pytest.raises(SystemExit, match="error: "):
        main(["report", str(tmp_path)])
