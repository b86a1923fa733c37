"""Typed Python facades over the job config tree (DESIGN.md §8).

``train(cfg)`` / ``serve(cfg)`` / ``dryrun(cfg)`` / ``bench(cfg)`` each
take a :class:`RunConfig`, run the corresponding workload through the
existing subsystems (``launch.engine.Trainer``, ``serve.ServeEngine``,
``launch.dryrun``, ``serve.bench``) and return a typed result object.
Every run creates a per-run directory (``rundir.make_run_dir``) holding
its exact ``config.json`` and, by default, its ``metrics.jsonl`` — the
reproducibility contract: the config that ran is always next to the
numbers it produced.

The legacy CLIs (``repro.launch.train`` / ``repro.launch.serve`` /
``benchmarks/serve_bench.py``) are thin flags->RunConfig adapters over
these facades, so the config-driven and flag-driven paths execute the
same jitted step bit for bit.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import pathlib
import warnings
from typing import Any, Dict, Iterator, List, Optional

from .config import DataSpec, RunConfig
from .rundir import make_run_dir

_DEPRECATION_WARNED: set = set()


def _make_run_tracker(cfg: RunConfig, run_dir: str):
    """The run's tracker per ``cfg.obs``: the jsonl default streams
    events/spans to ``<run_dir>/events.jsonl``."""
    from repro import obs as obs_lib

    path = cfg.obs.events_path or os.path.join(run_dir, "events.jsonl")
    return obs_lib.make_tracker(cfg.obs.tracker, path=path)


def _write_summary(run_dir: str, kind: str, summary: Dict[str, Any],
                   tracker) -> str:
    """Drop ``summary.json`` — the run's machine-readable digest
    (workload summary + the tracker's counter/span snapshot) that
    ``python -m repro report`` renders."""
    import json

    path = os.path.join(run_dir, "summary.json")
    data = {"kind": kind, "summary": summary, "obs": tracker.snapshot()}
    with open(path, "w") as fh:
        json.dump(data, fh, indent=2, default=str)
        fh.write("\n")
    return path


def warn_legacy(entrypoint: str, replacement: str) -> None:
    """One DeprecationWarning per legacy entry point per process."""
    if entrypoint in _DEPRECATION_WARNED:
        return
    _DEPRECATION_WARNED.add(entrypoint)
    warnings.warn(
        f"{entrypoint} flags are deprecated; use `{replacement}` with a "
        f"job file (see experiments/jobs/) — legacy flags keep working "
        f"through this adapter",
        DeprecationWarning, stacklevel=3)


def force_host_devices(n: int) -> None:
    """Give the CPU platform n host devices, one per worker — must run
    before jax initialises its backend.

    Only under ``JAX_PLATFORMS=cpu`` (tests, CI, the CLIs on a host
    without a chip). On any other platform the devices are chips, and a
    run that cannot reach them fails instead of falling back to fake CPU
    devices."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}").strip()


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is jax's own and is left as
    it is. Otherwise the cache lives at one fixed path inside the
    checkout, ``.jax_cache`` (git-ignored): the directory is part of
    the cache key, so only a path that never moves is ever hit again.
    Entry points call this before compiling anything; importing a
    module never does."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        # <checkout>/src/repro/run/facade.py -> <checkout>/.jax_cache
        path = str(pathlib.Path(__file__).resolve().parents[3]
                   / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# Result objects
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunResult:
    """Common shape: the config that ran, where it wrote, what it found."""

    config: RunConfig
    run_dir: str
    summary: Dict[str, Any]


@dataclasses.dataclass
class TrainResult(RunResult):
    metrics_path: str = ""
    state: Any = None                # final launch.engine.TrainState

    @property
    def first_loss(self) -> Optional[float]:
        return self.summary.get("first_loss")

    @property
    def final_loss(self) -> Optional[float]:
        return self.summary.get("final_loss")


@dataclasses.dataclass
class ServeResult(RunResult):
    metrics_path: str = ""
    outputs: List[List[int]] = dataclasses.field(default_factory=list)

    @property
    def tokens_per_s(self) -> float:
        return self.summary.get("tokens_per_s", 0.0)


@dataclasses.dataclass
class DryrunResult(RunResult):
    record_path: str = ""


@dataclasses.dataclass
class BenchResult(RunResult):
    @property
    def speedup(self) -> float:
        return self.summary.get("speedup", 0.0)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def _quadratic_setup(data: DataSpec, batch: int):
    """The paper's numerical setting as an engine workload: a strongly
    convex quadratic with per-worker gradient noise (Assumption 5)."""
    import jax
    import jax.numpy as jnp

    from repro.core import costfns

    cost = costfns.quadratic(jax.random.PRNGKey(data.seed), d=data.dim,
                             mu=data.mu, L=data.L, sigma=0.0)

    def loss_fn(values, batch_):
        w = values["w"]
        return cost.value(w) + w @ jnp.mean(batch_["eps"], 0), {}

    def batches(start: int = 0) -> Iterator[Dict[str, jax.Array]]:
        step = start
        base = jax.random.PRNGKey(data.seed + 1)
        while True:
            key = jax.random.fold_in(base, step)
            yield {"eps": data.noise
                   * jax.random.normal(key, (batch, data.dim))}
            step += 1

    values = {"w": jnp.ones((data.dim,)) * data.w0}
    return loss_fn, values, batches


def _model_setup(cfg: RunConfig):
    import dataclasses as _dc

    from repro.configs import get_config, reduced

    model_cfg = get_config(cfg.model.arch)
    if cfg.model.smoke:
        model_cfg = reduced(model_cfg)
    if cfg.model.param_dtype:
        model_cfg = _dc.replace(model_cfg, param_dtype=cfg.model.param_dtype)
    return model_cfg


def _job_devices(cfg: RunConfig) -> list:
    """The first ``mesh.devices`` devices jax has (all of them for 0).

    Asking for more than exist is an error: the run would otherwise
    record in its config.json a worker topology that never ran. So is
    landing on the CPU without ``JAX_PLATFORMS=cpu``: that is jax's
    fallback when no accelerator came up, not a platform the job chose."""
    import jax

    devices = jax.devices()
    if (devices[0].platform == "cpu"
            and os.environ.get("JAX_PLATFORMS") != "cpu"):
        raise RuntimeError(
            "jax found no accelerator and fell back to the CPU; set "
            "JAX_PLATFORMS=cpu to run this job on the CPU")
    want = cfg.mesh.devices or len(devices)
    if want > len(devices):
        hint = (" (host devices are set before jax initialises its "
                "backend, and this process had already initialised it)"
                if devices[0].platform == "cpu" else "")
        raise ValueError(f"mesh.devices={want} but jax has {len(devices)} "
                         f"{devices[0].platform} device(s){hint}")
    return devices[:want]


def _make_mesh(cfg: RunConfig, batch: int, strategy: str,
               needs_workers: bool):
    """Worker mesh over the job's devices, with the legacy CLI's
    validation messages."""
    from repro.launch.mesh import make_host_mesh

    scen = cfg.scenario
    devices = _job_devices(cfg)
    n_dev = len(devices)
    mesh = make_host_mesh(devices) if n_dev > 1 and batch % n_dev == 0 \
        else None
    if mesh is None and needs_workers:
        raise ValueError(
            f"strategy {strategy!r} needs >1 data-parallel workers: set "
            f"mesh.devices=N (and a train.batch divisible by N) on a "
            f"host with N devices")
    if scen.n_byz and mesh is None:
        raise ValueError(
            "scenario.n_byz needs >1 data-parallel workers: set "
            "mesh.devices=N and a train.batch divisible by N")
    if mesh is None and (scen.f or scen.aggregator != "mean"):
        print("warning: single worker — no aggregation runs, so "
              "scenario.aggregator/f are inactive (set mesh.devices=N "
              "to exercise them)")
    return mesh


def train(cfg: RunConfig) -> TrainResult:
    """Run the training workload a :class:`RunConfig` describes."""
    if cfg.train is None:
        raise ValueError("job config has no `train` section")
    if cfg.mesh.devices:
        force_host_devices(cfg.mesh.devices)

    import jax

    from repro.comm import resolve as resolve_comm
    from repro.comm.policy import resolve_policy
    from repro.data import make_batch_iterator
    from repro.launch.engine import (Trainer, TrainerConfig, TrainSettings,
                                     TRAIN_STRATEGIES)
    from repro.models import model as M
    from repro.models.nn import split_params
    from repro.optim import adamw, sgd

    from repro import net as net_lib

    tspec, scen = cfg.train, cfg.scenario
    if tspec.strategy not in TRAIN_STRATEGIES:
        raise ValueError(f"unknown train strategy {tspec.strategy!r}; "
                         f"known: {sorted(TRAIN_STRATEGIES)}")
    # scenario.net: validate + swap the relay channel in before the
    # settings freeze (apply_to_comm is a no-op without a relay tier).
    comm_cfg = net_lib.apply_to_comm(scen.net, resolve_comm(scen.comm))
    settings = TrainSettings(
        aggregator=scen.aggregator, f=scen.f, n_byz=scen.n_byz,
        byz_mode=scen.attack, microbatches=tspec.microbatches,
        clip_norm=tspec.clip_norm, echo_k=scen.echo_k, echo_r=scen.echo_r,
        moe_impl=cfg.mesh.moe_impl, fsdp=tspec.strategy == "fsdp",
        comm=comm_cfg,
        policy=resolve_policy(scen.comm), ef=scen.comm.ef)
    optimizers = {"adamw": adamw, "sgd": sgd}
    if tspec.optimizer not in optimizers:
        raise ValueError(f"unknown train.optimizer {tspec.optimizer!r}; "
                         f"known: {sorted(optimizers)}")
    opt = optimizers[tspec.optimizer](tspec.lr)

    quadratic = scen.data.source == "quadratic"
    if not quadratic and cfg.model is None:
        raise ValueError("job config needs a `model` section unless "
                         "scenario.data.source == 'quadratic'")
    if quadratic:
        loss_fn, values, quad_batches = _quadratic_setup(scen.data,
                                                         tspec.batch)
        model_cfg = None
    else:
        loss_fn = None
        model_cfg = _model_setup(cfg)

    mesh = _make_mesh(cfg, tspec.batch, tspec.strategy,
                      needs_workers=tspec.strategy in ("fsdp", "echo_dp"))

    run_dir = make_run_dir(cfg, "train")
    metrics_path = tspec.metrics_path or os.path.join(run_dir,
                                                      "metrics.jsonl")

    from repro import obs as obs_lib

    tracker = _make_run_tracker(cfg, run_dir)
    with obs_lib.use_tracker(tracker):
        trainer = Trainer(tspec.strategy, model_cfg, opt, settings, mesh,
                          tspec.batch,
                          TrainerConfig(log_every=tspec.log_every,
                                        ckpt_dir=tspec.ckpt_dir,
                                        ckpt_every=tspec.ckpt_every,
                                        resume=tspec.resume,
                                        metrics_path=metrics_path,
                                        profile_steps=tspec.profile_steps,
                                        profile_dir=os.path.join(
                                            run_dir, "profile")),
                          loss_fn=loss_fn,
                          hooks=obs_lib.TrackerHook())
        comm_tag = (f" comm={scen.comm.channel}/{scen.comm.codec}"
                    if (scen.comm.channel,
                        scen.comm.codec) != ("ideal", "fp32")
                    else "")
        if scen.comm.policy != "static":
            comm_tag += f" policy={scen.comm.policy}"
        if scen.comm.ef:
            comm_tag += " ef=on"
        if net_lib.net_active(scen.net):
            # resolve the hearing graph against the workers that ran and
            # emit the run's net.* digest next to the comm events. The
            # coarse driver's echo basis is a parameter-server downlink,
            # so the graph is informational here (DESIGN.md §15); the
            # slot-level simulation enforces it per worker.
            graph = net_lib.resolve_net(scen.net, trainer.n_workers)
            obs_lib.event("net.topology", topology=scen.net.topology,
                          n=graph.n, edges=graph.edge_count(),
                          complete=graph.is_complete,
                          degree=scen.net.degree)
            obs_lib.counter("net.hearing_edges", graph.edge_count())
            comm_tag += f" net={scen.net.topology}"
            if scen.net.relays:
                ch = settings.comm.channel
                obs_lib.event("net.channel", relays=scen.net.relays,
                              byz_relays=scen.net.byz_relays,
                              broadcast=scen.net.broadcast,
                              protected=ch.protected,
                              price_factor=ch.price_factor())
                if scen.net.broadcast == "bracha":
                    outcome = net_lib.simulate_bracha(
                        scen.net.relays, scen.net.byz_relays)
                elif scen.net.broadcast == "direct":
                    outcome = net_lib.simulate_plain_relay(
                        scen.net.relays, scen.net.byz_relays)
                else:
                    outcome = None
                if outcome is not None:
                    obs_lib.event("net.broadcast",
                                  discipline=scen.net.broadcast,
                                  **outcome.as_event())
                comm_tag += (f" relays={scen.net.relays}"
                             f"({scen.net.broadcast})")
        print(f"strategy={tspec.strategy} workers={trainer.n_workers} "
              f"aggregator={scen.aggregator} f={scen.f}{comm_tag} "
              f"run_dir={run_dir}")

        if quadratic:
            state = trainer.init_state(values)
            it = quad_batches(start=state.step)
        else:
            # no name keeps the initial values alive: init_state copies
            # them, and at published widths a second copy of the weights
            # is what decides whether a step fits the chip
            state = trainer.init_state(split_params(
                M.init_params(model_cfg, jax.random.PRNGKey(0)))[0])
            # start=state.step: a resumed run continues the data stream
            # instead of re-consuming batches the checkpointed run saw.
            it = make_batch_iterator(model_cfg, tspec.batch, tspec.seq,
                                     seed=scen.data.seed, start=state.step)
        if state.step:
            print(f"resumed from step {state.step}")

        mesh_ctx = jax.set_mesh(mesh) if mesh is not None \
            else contextlib.nullcontext()
        with mesh_ctx:
            state, summary = trainer.fit(state, it, tspec.steps)
        trainer.close()
        _write_summary(run_dir, "train", summary, tracker)
    tracker.close()
    return TrainResult(config=cfg, run_dir=run_dir, summary=summary,
                       metrics_path=metrics_path, state=state)


def print_train_summary(result: TrainResult) -> None:
    """The CLI's closing lines (shared by legacy and config paths)."""
    summary, tspec = result.summary, result.config.train
    if not summary["rounds"]:
        print(f"nothing to do: resumed at or past train.steps="
              f"{tspec.steps}")
        return
    print(f"final loss {summary['final_loss']:.4f} "
          f"(from {summary['first_loss']:.4f}) in {summary['wall_s']}s")
    if "echo_rate" in summary:
        print(f"echo rounds {summary['echo_rounds']}/{summary['rounds']} "
              f"({100.0 * summary['echo_rate']:.1f}%); cumulative bits "
              f"{summary['bits_sent']:.3e} vs all-raw baseline "
              f"{summary['bits_baseline']:.3e} "
              f"({100.0 * summary['bits_saving']:.1f}% saved)")
    if summary.get("codec_final") is not None:
        print(f"policy {summary['policy']}: "
              f"{summary['codec_switches']} codec switches, settled on "
              f"codec={summary['codec_final']} "
              f"echo_r={summary['echo_r_final']:.3f}")
    if tspec.ckpt_dir:
        print("checkpoint saved to", tspec.ckpt_dir)


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def serve(cfg: RunConfig) -> ServeResult:
    """Run the serving workload: a seeded synthetic mixed-length request
    trace through :class:`repro.serve.ServeEngine`.

    ``mesh.devices`` picks the devices exactly like the train facade;
    with more than one device the engine runs tensor-parallel over a
    (data=1, model=n) mesh (params + page pools sharded by the
    logical-axis rules), honouring ``mesh.moe_impl``.
    """
    if cfg.serve is None:
        raise ValueError("job config has no `serve` section")
    if cfg.model is None:
        raise ValueError("serving needs a `model` section")
    if cfg.mesh.devices:
        force_host_devices(cfg.mesh.devices)

    import jax
    import numpy as np

    from repro.launch.mesh import make_host_mesh
    from repro.models import model as M
    from repro.serve import ServeConfig, ServeEngine

    spec = cfg.serve
    model_cfg = _model_setup(cfg)
    if not model_cfg.has_decode:
        raise ValueError(f"{cfg.model.arch} is encoder-only: no decode "
                         f"step")

    devices = _job_devices(cfg)
    mesh = make_host_mesh(devices, model=len(devices)) \
        if len(devices) > 1 else None

    run_dir = make_run_dir(cfg, "serve")
    metrics_path = spec.metrics_path or os.path.join(run_dir,
                                                     "metrics.jsonl")

    from repro import obs as obs_lib

    tracker = _make_run_tracker(cfg, run_dir)
    with obs_lib.use_tracker(tracker):
        params = M.init_params(model_cfg, jax.random.PRNGKey(spec.seed))
        engine = ServeEngine(model_cfg, params, ServeConfig(
            max_batch=spec.max_batch, page_size=spec.page_size,
            num_pages=spec.num_pages,
            max_blocks_per_seq=spec.max_blocks_per_seq,
            token_budget=spec.token_budget,
            decode_quantum=spec.decode_quantum,
            prefill_chunk=spec.prefill_chunk,
            prefix_cache=spec.prefix_cache, metrics_path=metrics_path,
            log_every=spec.log_every, sampling=spec.sampling),
            mesh=mesh, moe_impl=cfg.mesh.moe_impl,
            hooks=obs_lib.TrackerHook())

        rng = np.random.default_rng(spec.seed)
        # a shared "system prompt" every request starts with — the prefix
        # cache turns its prefill into page adoptions after the first
        # request
        shared = rng.integers(0, model_cfg.vocab_size,
                              size=spec.shared_prefix_len).tolist() \
            if spec.shared_prefix_len else []
        handles = []
        for i in range(spec.requests):
            plen = int(rng.integers(2, max(spec.prompt_len, 2) + 1))
            gen = int(rng.integers(1, max(spec.gen, 1) + 1))
            prompt = shared + rng.integers(0, model_cfg.vocab_size,
                                           size=plen).tolist()
            handles.append(engine.submit(
                prompt, max_new=gen, priority=spec.priority,
                deadline_s=spec.deadline_s or None,
                tenant=f"t{i % max(spec.tenants, 1)}"))

        engine.drain(max_steps=100 * spec.requests * (spec.gen + 2))
        engine.sched.check_invariants()
        summary = engine.summary()
        engine.close()
        _write_summary(run_dir, "serve", summary, tracker)
    tracker.close()
    if not all(h.done for h in handles):
        raise RuntimeError("drain left unfinished requests")
    return ServeResult(config=cfg, run_dir=run_dir, summary=summary,
                       metrics_path=metrics_path,
                       outputs=[list(h.tokens) for h in handles])


def print_serve_summary(result: ServeResult) -> None:
    cfg, spec, summary = result.config, result.config.serve, result.summary
    print(f"arch={cfg.model.arch} requests={spec.requests} "
          f"lanes={spec.max_batch} pages={spec.num_pages}"
          f"x{spec.page_size} run_dir={result.run_dir}")
    print(f"generated {summary['tokens_generated']} tokens in "
          f"{summary['wall_s']}s ({summary['tokens_per_s']} tok/s), "
          f"{summary['preemptions']} preemptions")
    print(f"latency p50={summary['latency_p50_s']}s "
          f"p99={summary['latency_p99_s']}s "
          f"ttft p50={summary['ttft_p50_s']}s "
          f"p99={summary['ttft_p99_s']}s "
          f"itl p50={summary['itl_p50_s']}s")
    if summary.get("prefix_hit_tokens"):
        print(f"prefix cache: hit rate "
              f"{100.0 * summary['prefix_hit_rate']:.1f}% "
              f"({summary['prefix_hit_tokens']} tokens adopted, "
              f"{summary['cow_copies']} CoW copies)")


# ---------------------------------------------------------------------------
# dryrun
# ---------------------------------------------------------------------------


def dryrun(cfg: RunConfig) -> DryrunResult:
    """Lower+compile the job's (arch, shape, variant) on the production
    mesh and record the analysis JSON.

    NOTE: ``repro.launch.dryrun`` forces 512 fake host devices at import,
    which must happen before jax initialises — call this facade first
    thing in a fresh process (the ``python -m repro dryrun`` CLI does).
    """
    if cfg.dryrun is None:
        raise ValueError("job config has no `dryrun` section")
    if cfg.model is None:
        raise ValueError("dryrun needs a `model` section")
    import json

    from repro.launch import dryrun as dry

    spec = cfg.dryrun
    variant = spec.variant
    if variant is None:
        strategy = cfg.train.strategy if cfg.train else "replicated"
        variant = {"replicated": "baseline"}.get(strategy, strategy)
    run_dir = make_run_dir(cfg, "dryrun")
    rec = dry.dryrun_pair(cfg.model.arch, spec.shape, spec.multi_pod,
                          moe_impl=cfg.mesh.moe_impl,
                          compile_=spec.compile, variant=variant,
                          param_dtype=cfg.model.param_dtype)
    os.makedirs(spec.out, exist_ok=True)
    tag = (f"{cfg.model.arch}__{spec.shape}__"
           f"{'2x16x16' if spec.multi_pod else '16x16'}")
    if variant != "baseline":
        tag += f"__{variant}"
    record_path = os.path.join(spec.out, tag + ".json")
    with open(record_path, "w") as fh:
        json.dump(rec, fh, indent=2)
    with open(os.path.join(run_dir, "record.json"), "w") as fh:
        json.dump(rec, fh, indent=2)
    return DryrunResult(config=cfg, run_dir=run_dir, summary=rec,
                        record_path=record_path)


# ---------------------------------------------------------------------------
# bench
# ---------------------------------------------------------------------------


def bench(cfg: RunConfig) -> BenchResult:
    """Continuous-batching vs fixed-batch serving benchmark over a
    Poisson trace (``repro.serve.bench``)."""
    if cfg.bench is None:
        raise ValueError("job config has no `bench` section")
    if cfg.model is None:
        raise ValueError("bench needs a `model` section")
    import json

    from repro.serve.bench import run_bench

    run_dir = make_run_dir(cfg, "bench")
    summary = run_bench(cfg.model.arch, cfg.bench)
    with open(os.path.join(run_dir, "result.json"), "w") as fh:
        json.dump(summary, fh, indent=2)
    return BenchResult(config=cfg, run_dir=run_dir, summary=summary)
