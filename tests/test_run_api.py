"""The declarative job API end to end (repro.run facades + the unified
``python -m repro`` CLI + legacy-shim equivalence). Everything here runs
on the single in-process CPU device (mesh.devices=0)."""
import json
import os

import pytest

from repro.run import (DataSpec, MeshSpec, ModelSpec, RunConfig,
                       ScenarioSpec, ServeSpec, TrainSpec, facade)


def _quad_cfg(tmp_path, steps=3, name="quad"):
    return RunConfig(
        name=name,
        model=None,
        mesh=MeshSpec(devices=0),
        scenario=ScenarioSpec(
            aggregator="mean", f=0,
            data=DataSpec(source="quadratic", dim=16, mu=0.5, L=1.0,
                          noise=1e-3)),
        train=TrainSpec(strategy="replicated", steps=steps,
                        batch=4, optimizer="sgd", lr=0.1, log_every=100),
        runs_root=str(tmp_path / "runs"))


def test_train_facade_quadratic_and_run_dir(tmp_path, capsys):
    cfg = _quad_cfg(tmp_path)
    result = facade.train(cfg)
    assert result.config == cfg
    assert result.summary["rounds"] == 3
    assert result.final_loss < result.first_loss   # SGD descends
    # per-run directory: exact config next to the metrics it produced
    assert os.path.dirname(result.metrics_path) == result.run_dir
    saved = RunConfig.load(os.path.join(result.run_dir, "config.json"))
    assert saved == cfg
    records = [json.loads(l) for l in
               open(result.metrics_path).read().splitlines()]
    assert len(records) == 3 and records[0]["step"] == 0
    assert records[0]["loss"] == result.first_loss


def test_run_dirs_never_collide(tmp_path):
    cfg = _quad_cfg(tmp_path, steps=1)
    a = facade.train(cfg)
    b = facade.train(cfg)             # same config, same second is fine
    assert a.run_dir != b.run_dir
    assert os.path.exists(os.path.join(a.run_dir, "metrics.jsonl"))
    assert os.path.exists(os.path.join(b.run_dir, "metrics.jsonl"))


def test_train_facade_validation_errors(tmp_path):
    with pytest.raises(ValueError, match="no `train` section"):
        facade.train(RunConfig(train=None))
    with pytest.raises(ValueError, match="bogus.*known"):
        facade.train(RunConfig(mesh=MeshSpec(devices=0),
                               train=TrainSpec(strategy="bogus"),
                               runs_root=str(tmp_path)))
    with pytest.raises(ValueError, match="optimizer"):
        facade.train(RunConfig(mesh=MeshSpec(devices=0),
                               train=TrainSpec(optimizer="lion"),
                               runs_root=str(tmp_path)))
    bad = _quad_cfg(tmp_path)
    with pytest.raises(ValueError, match="model"):
        facade.train(RunConfig(model=None, mesh=MeshSpec(devices=0),
                               train=TrainSpec(),
                               runs_root=str(tmp_path)))
    assert bad.model is None          # quadratic path needs no model


def test_more_devices_than_exist_is_an_error(tmp_path):
    """A job never runs on fewer devices than its config.json records:
    this process has one CPU device, so asking for two fails up front."""
    import dataclasses

    import jax
    cfg = dataclasses.replace(_quad_cfg(tmp_path), mesh=MeshSpec(devices=2))
    assert len(jax.devices()) == 1
    with pytest.raises(ValueError, match="mesh.devices=2 but jax has 1"):
        facade.train(cfg)
    with pytest.raises(ValueError, match="mesh.devices=2 but jax has 1"):
        facade.serve(RunConfig(model=ModelSpec(smoke=True),
                               mesh=MeshSpec(devices=2),
                               serve=ServeSpec(requests=1),
                               runs_root=str(tmp_path)))


def test_cpu_fallback_is_an_error(monkeypatch, tmp_path):
    """This process's jax is on the CPU. Without JAX_PLATFORMS=cpu that
    reads as an accelerator that never came up, so train and serve
    refuse to run, with mesh.devices unset as with it set."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        facade.train(_quad_cfg(tmp_path))
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        facade.serve(RunConfig(model=ModelSpec(smoke=True),
                               mesh=MeshSpec(devices=0),
                               serve=ServeSpec(requests=1),
                               runs_root=str(tmp_path)))


def _profiled(cfg, steps):
    import dataclasses
    return dataclasses.replace(
        cfg, train=dataclasses.replace(cfg.train, profile_steps=steps))


def test_profile_that_cannot_be_taken_fails_the_run(tmp_path):
    """A run that asked for a profile either writes one or fails: here
    another trace already holds the profiler. The window opens after the
    first round, so that round (and its compilation) ran first; a fit too
    short to hold a profiled round fails before it starts."""
    import glob

    import jax
    cfg = _profiled(_quad_cfg(tmp_path, steps=2), 1)
    jax.profiler.start_trace(str(tmp_path / "outer"))
    try:
        with pytest.raises(RuntimeError, match="already been started"):
            facade.train(cfg)
    finally:
        jax.profiler.stop_trace()
    (metrics,) = glob.glob(str(tmp_path / "runs" / "*" / "metrics.jsonl"))
    assert [json.loads(l)["step"] for l in
            open(metrics).read().splitlines()] == [0]
    with pytest.raises(ValueError, match="at least 2 rounds"):
        facade.train(_profiled(_quad_cfg(tmp_path, steps=1), 1))


def test_profile_window_opens_after_the_first_round(tmp_path):
    """train.profile_steps=N traces rounds 2..N+1 of fit(): the first
    round compiles outside the window."""
    import glob

    result = facade.train(_profiled(_quad_cfg(tmp_path, steps=4), 2))
    recs = [json.loads(l) for l in
            open(os.path.join(result.run_dir, "events.jsonl"))]
    marks = [r["kind"] if r["kind"] != "span" else r["path"] for r in recs
             if r["kind"] in ("train.profile_start", "train.profile_stop")
             or r.get("path") == "train.round"]
    assert marks == ["train.round", "train.profile_start", "train.round",
                     "train.round", "train.profile_stop", "train.round"]
    assert glob.glob(os.path.join(result.run_dir, "profile", "**",
                                  "*.xplane.pb"), recursive=True)


@pytest.mark.parametrize("platforms,forced", [("cpu", True),
                                              ("tpu", False), ("", False)])
def test_host_devices_are_forced_only_on_cpu(monkeypatch, platforms,
                                             forced):
    """Fake host devices exist only under JAX_PLATFORMS=cpu: elsewhere
    mesh.devices counts chips, and a chip that cannot be reached must
    fail rather than turn into CPU devices."""
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    facade.force_host_devices(4)
    flags = os.environ.get("XLA_FLAGS", "")
    assert ("xla_force_host_platform_device_count=4" in flags) == forced


def test_compile_cache_placement(monkeypatch, tmp_path):
    """The cache sits where JAX_COMPILATION_CACHE_DIR says; unset, at one
    fixed, git-ignored path inside the checkout."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert facade.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = facade.enable_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert facade.enable_compile_cache() == path      # fixed
        with open(os.path.join(root, ".gitignore")) as fh:
            assert ".jax_cache/" in fh.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()


def test_cli_show_and_list(tmp_path, capsys):
    from repro.__main__ import main

    job = tmp_path / "job.json"
    _quad_cfg(tmp_path).save(str(job))
    assert main(["show", "--config", str(job),
                 "--set", "train.steps=9"]) == 0
    out = capsys.readouterr().out
    shown = RunConfig.from_json(out)
    assert shown.train.steps == 9

    assert main(["list"]) == 0
    out = capsys.readouterr().out
    assert "train_strategies: echo_dp, fsdp, replicated" in out
    assert "attacks:" in out and "sign_flip" in out


def test_cli_friendly_errors(tmp_path):
    """Bad --set paths, bad job files and missing files exit with the
    did-you-mean message, not a traceback."""
    from repro.__main__ import main

    job = tmp_path / "job.json"
    _quad_cfg(tmp_path).save(str(job))
    with pytest.raises(SystemExit, match="no field 'stepz'"):
        main(["train", "--config", str(job), "--set", "train.stepz=3"])
    with pytest.raises(SystemExit, match="error:"):
        main(["show", "--config", str(tmp_path / "nope.json")])
    bad = tmp_path / "bad.json"
    bad.write_text('{"schema_version": 1, "trian": {}}')
    with pytest.raises(SystemExit, match="train"):
        main(["show", "--config", str(bad)])


def test_cli_train_runs_job_file(tmp_path, capsys):
    from repro.__main__ import main

    job = tmp_path / "job.json"
    _quad_cfg(tmp_path).save(str(job))
    assert main(["train", "--config", str(job),
                 "--set", "train.steps=2"]) == 0
    out = capsys.readouterr().out
    assert "final loss" in out
    runs = os.listdir(tmp_path / "runs")
    assert len(runs) == 1
    saved = RunConfig.load(str(tmp_path / "runs" / runs[0] /
                               "config.json"))
    assert saved.train.steps == 2     # the override is what actually ran


# ---------------------------------------------------------------------------
# Legacy shim: single DeprecationWarning + bitwise-identical first step
# ---------------------------------------------------------------------------

_LEGACY_FLAGS = ["--arch", "qwen3-0.6b", "--smoke", "--steps", "1",
                 "--devices", "0", "--batch", "4", "--seq", "32",
                 "--aggregator", "mean"]


def _first_record(path):
    return json.loads(open(path).read().splitlines()[0])


def test_legacy_train_flags_bitwise_equal_config_path(tmp_path,
                                                      monkeypatch):
    """The deprecated flag CLI and the config-driven CLI run the same
    jitted step: first-step metrics are bitwise identical."""
    from repro.__main__ import main as repro_main
    from repro.launch import train as legacy

    monkeypatch.chdir(tmp_path)       # legacy default runs_root is CWD-rel
    legacy_metrics = tmp_path / "legacy.jsonl"
    with pytest.warns(DeprecationWarning):
        legacy.main(_LEGACY_FLAGS + ["--metrics", str(legacy_metrics)])

    cfg_metrics = tmp_path / "config.jsonl"
    job = tmp_path / "job.json"
    cfg = RunConfig(
        name="equivalence",
        model=ModelSpec(arch="qwen3-0.6b", smoke=True),
        mesh=MeshSpec(devices=0),
        scenario=ScenarioSpec(aggregator="mean"),
        train=TrainSpec(strategy="replicated", steps=1, batch=4, seq=32,
                        metrics_path=str(cfg_metrics)),
        runs_root=str(tmp_path / "runs"))
    cfg.save(str(job))
    assert repro_main(["train", "--config", str(job)]) == 0

    a, b = _first_record(legacy_metrics), _first_record(cfg_metrics)
    assert a["loss"] == b["loss"]                  # bitwise (json repr)
    assert a["bits"] == b["bits"] and a["step"] == b["step"]


def test_legacy_adapter_equals_hand_built_config():
    """config_from_flags maps the default flag namespace onto the same
    tree a job file would load (the adapter IS the compatibility
    contract)."""
    import argparse

    from repro.launch.train import config_from_flags

    ns = argparse.Namespace(
        arch="qwen3-0.6b", smoke=True, strategy="echo_dp", steps=4,
        batch=8, seq=64, lr=3e-4, aggregator="cgc", f=1, n_byz=0,
        byz_mode="sign_flip", microbatches=1, clip_norm=0.0, echo_k=4,
        echo_r=0.9, devices=8, ckpt_dir=None, ckpt_every=0, resume=False,
        metrics=None, log_every=5)
    cfg = config_from_flags(ns)
    assert cfg.model == ModelSpec(arch="qwen3-0.6b", smoke=True)
    assert cfg.train.strategy == "echo_dp" and cfg.scenario.f == 1
    assert RunConfig.from_json(cfg.to_json()) == cfg


def test_legacy_warning_fires_exactly_once(monkeypatch):
    import warnings

    monkeypatch.setattr(facade, "_DEPRECATION_WARNED", set())
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        facade.warn_legacy("repro.launch.train", "python -m repro train")
        facade.warn_legacy("repro.launch.train", "python -m repro train")
        facade.warn_legacy("repro.launch.serve", "python -m repro serve")
    deps = [w for w in caught if w.category is DeprecationWarning]
    assert len(deps) == 2             # once per entry point, not per call
    assert "python -m repro train" in str(deps[0].message)
