"""Distributed trainer CLI — a deprecation shim over ``repro.run``.

The step builders that used to live here are strategies in
``launch/engine.py`` (back-compat ``make_*_train_step`` wrappers below);
the script entry point

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
        --strategy echo_dp

is now a flags->RunConfig adapter over the declarative job API: it emits
one DeprecationWarning, builds the equivalent :class:`repro.run.
RunConfig` and calls ``repro.run.train`` — the same facade
``python -m repro train --config job.json`` runs, so legacy flag
invocations and config-driven runs execute the same jitted step bit for
bit (DESIGN.md §8). ``--devices N`` (default 8) is the job's device
count: the first N chips, or under ``JAX_PLATFORMS=cpu`` N host devices
set before jax initialises; 0 uses every device.
"""
from __future__ import annotations

import jax

from repro.launch.engine import (EchoDpStrategy, FsdpStrategy,  # noqa: F401
                                 MetricsSink, ReplicatedStrategy, StepBundle,
                                 STRATEGIES, Trainer, TrainerConfig,
                                 TrainSettings, TrainState, batch_shardings,
                                 opt_state_shardings, param_shardings)


# ---------------------------------------------------------------------------
# Back-compat step builders (thin shims over the engine strategies)
# ---------------------------------------------------------------------------


def make_train_step(cfg, opt, settings: TrainSettings, mesh,
                    global_batch: int):
    """Replicated CGC train step: (step_fn, ctx). See ReplicatedStrategy."""
    b = ReplicatedStrategy().build(cfg, opt, settings, mesh, global_batch)
    if mesh is None or not b.ctx.batch_axes:
        return jax.jit(b.fn), b.ctx
    return b.fn, b.ctx


def make_fsdp_train_step(cfg, opt, settings: TrainSettings, mesh,
                         global_batch: int):
    """FSDP train step: (step_fn, ctx, (value_shardings, plan)).
    See FsdpStrategy."""
    b = FsdpStrategy().build(cfg, opt, settings, mesh, global_batch)
    return b.fn, b.ctx, (b.value_shardings, b.plan)


def make_echo_train_step(cfg, opt, settings: TrainSettings, mesh,
                         global_batch: int):
    """Echo-compressed DP train step: (step_fn, ctx). See EchoDpStrategy."""
    b = EchoDpStrategy().build(cfg, opt, settings, mesh, global_batch)
    return b.fn, b.ctx


# ---------------------------------------------------------------------------
# Script entry: legacy flags -> RunConfig adapter over repro.run.train
# ---------------------------------------------------------------------------


def config_from_flags(args) -> "run.RunConfig":
    """The flags->RunConfig adapter: one legacy argparse namespace maps
    to exactly the job tree the unified CLI would load, so both paths
    run the same jitted step bit for bit."""
    from repro import run
    return run.RunConfig(
        name=f"{args.arch}-{args.strategy}",
        model=run.ModelSpec(arch=args.arch, smoke=args.smoke),
        mesh=run.MeshSpec(devices=args.devices),
        scenario=run.ScenarioSpec(
            aggregator=args.aggregator, attack=args.byz_mode, f=args.f,
            n_byz=args.n_byz, echo_k=args.echo_k, echo_r=args.echo_r),
        train=run.TrainSpec(
            strategy=args.strategy, steps=args.steps, batch=args.batch,
            seq=args.seq, lr=args.lr, microbatches=args.microbatches,
            clip_norm=args.clip_norm, log_every=args.log_every,
            ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
            resume=args.resume, metrics_path=args.metrics))


def main(argv=None):
    import argparse

    from repro.run import facade

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--strategy", default="replicated",
                    choices=sorted(STRATEGIES))
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--aggregator", default="cgc")
    ap.add_argument("--f", type=int, default=0)
    ap.add_argument("--n-byz", type=int, default=0)
    ap.add_argument("--byz-mode", default="sign_flip")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--clip-norm", type=float, default=0.0)
    ap.add_argument("--echo-k", type=int, default=4)
    ap.add_argument("--echo-r", type=float, default=0.9)
    ap.add_argument("--devices", type=int, default=8,
                    help="devices the job uses: the first N chips, or N "
                         "host devices under JAX_PLATFORMS=cpu (0: all)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--metrics", default=None,
                    help="jsonl metrics sink path")
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args(argv)

    facade.warn_legacy("repro.launch.train", "python -m repro train")
    try:
        result = facade.train(config_from_flags(args))
    except ValueError as e:
        raise SystemExit(str(e)) from None
    facade.print_train_summary(result)
    return result.summary


if __name__ == "__main__":
    main()
