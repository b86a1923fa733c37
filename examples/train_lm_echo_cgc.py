"""End-to-end driver: train a language model with CGC-filtered aggregation.

    PYTHONPATH=src python examples/train_lm_echo_cgc.py \
        --preset demo --steps 300            # ~20M params, CPU-friendly
    PYTHONPATH=src python examples/train_lm_echo_cgc.py \
        --preset 100m --steps 200            # ~100M params (slow on CPU)

The trainer is the production path from repro.launch.engine: a Trainer
driver over the replicated strategy — data-parallel workers (simulated
in-process on CPU; mesh shards on real hardware), CGC aggregation over
per-worker gradients, AdamW, complete (values, opt_state, step)
checkpoints, deterministic synthetic data. ``--byz K`` makes K workers
Byzantine to demonstrate the filter on a real model. With a single
device the "workers" collapse to one — pass --devices 8 for true
multi-worker DP: 8 chips, or under JAX_PLATFORMS=cpu 8 forked CPU
devices.
"""
import argparse
import contextlib
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=["demo", "100m"], default="demo")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--aggregator", default="cgc")
    ap.add_argument("--f", type=int, default=1)
    ap.add_argument("--byz", type=int, default=0)
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--metrics", default=None,
                    help="jsonl per-round metrics sink")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    from repro.run.facade import force_host_devices

    if args.devices > 1:
        force_host_devices(args.devices)

    import jax

    from repro.configs.base import ModelConfig
    from repro.data import make_batch_iterator
    from repro.launch.engine import Trainer, TrainerConfig, TrainSettings
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as M
    from repro.models.nn import count_params, split_params
    from repro.optim import adamw, linear_warmup_cosine

    if args.preset == "demo":
        cfg = ModelConfig(name="lm-demo-20m", family="dense", num_layers=6,
                          d_model=320, num_heads=8, num_kv_heads=4,
                          d_ff=1280, vocab_size=8192, vocab_round=64,
                          qk_norm=True, tie_embeddings=True,
                          dtype="float32")
    else:
        cfg = ModelConfig(name="lm-100m", family="dense", num_layers=12,
                          d_model=512, num_heads=8, num_kv_heads=8,
                          d_ff=2048, vocab_size=32000, vocab_round=64,
                          qk_norm=True, dtype="float32")

    mesh = None
    if args.devices > 1:
        devices = jax.devices()
        if len(devices) < args.devices:
            raise SystemExit(f"--devices {args.devices} but jax has "
                             f"{len(devices)} {devices[0].platform} "
                             f"device(s)")
        mesh = make_host_mesh(devices[:args.devices])

    opt = adamw(linear_warmup_cosine(args.lr, 20, args.steps),
                weight_decay=0.01)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    values, _ = split_params(params)
    print(f"model {cfg.name}: {count_params(values):,d} params; "
          f"devices={args.devices} aggregator={args.aggregator} "
          f"f={args.f} byz={args.byz}")

    settings = TrainSettings(aggregator=args.aggregator, f=args.f,
                             n_byz=args.byz, byz_mode="large_norm")
    trainer = Trainer("replicated", cfg, opt, settings, mesh, args.batch,
                      TrainerConfig(log_every=args.log_every,
                                    ckpt_dir=args.ckpt_dir,
                                    metrics_path=args.metrics))
    state = trainer.init_state(values)
    it = make_batch_iterator(cfg, args.batch, args.seq, seed=0)

    t0 = time.time()
    mesh_ctx = jax.set_mesh(mesh) if mesh is not None \
        else contextlib.nullcontext()
    with mesh_ctx:
        state, summary = trainer.fit(state, it, args.steps)
    trainer.close()
    dt = time.time() - t0
    tok_s = args.steps * args.batch * args.seq / dt
    print(f"final loss {summary['final_loss']:.4f} "
          f"(from {summary['first_loss']:.4f}) in {dt:.1f}s "
          f"({tok_s:,.0f} tok/s)")
    if args.ckpt_dir:
        print("checkpoint written to", args.ckpt_dir)
    assert summary["final_loss"] < summary["first_loss"], \
        "loss did not improve"


if __name__ == "__main__":
    main()
