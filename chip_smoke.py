"""Bring-up check: the trainer, the server and the paper's round on a TPU.

    python chip_smoke.py              # one chip: train, serve, protocol
    python chip_smoke.py --chips 4    # echo-DP vs replicated CGC, 4 chips

With no option, three phases run in this one process, in order, each
through the entry point a user calls, and each prints one line:

  train     ``repro.run.train``: qwen3-0.6b at its published widths,
            replicated strategy, mean aggregation, adamw, a few steps on
            the seeded synthetic token stream. The loss must stay finite
            and end lower than it started.
  serve     ``repro.run.serve``: qwen3-0.6b, 8 greedy requests (prompts
            up to 128 tokens, up to 32 new) over 4 lanes and pages of
            16 tokens. Every request must finish. Then one paged
            decode-attention step of the compiled Pallas kernel is
            checked against the gather-then-attend reference on the same
            seeded page pool.
  protocol  ``core.protocol.run_training``: the paper's Algorithm 1 in
            its single-device simulation of n workers, at the paper's
            headline setting (n=100, f=10 sign-flipping workers,
            sigma=0.1) on a d=4096 quadratic. The CGC round runs the
            fused Pallas kernel. The distance to the optimum must fall.

Then every kernel backend any phase traced must have resolved to
``pallas``, and no ``REPRO_*_BACKEND`` override may be set.

``--chips 4`` runs only the four-chip phase: ``repro.run.train`` on
qwen3-0.6b at published widths with one worker per chip, f=1 and one
sign-flipping worker, as echo-DP and, for comparison, as replicated CGC
on the same data and steps. It prints each run's loss, echo-DP's echo
rate and bits against the all-raw baseline, and one line per memory cut.

The weights are random from a fixed seed and all data is generated from
seeds. The script exits nonzero and prints no result line when any phase
fails or jax finds no TPU; otherwise its last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

ARCH = "qwen3-0.6b"
RUNS = os.path.join(REPO, "experiments", "runs")

# The four-chip phase keeps every width and cuts only what echo-DP's
# memory forces on a 16 GB chip. Its optimistic step may not donate its
# inputs (a failed echo round re-runs them exactly), so the weights and
# the optimizer state are live twice, and the basis holds echo_k fp32
# copies of the gradient. Compiled for a v5e, the optimistic step with
# these cuts plans 12 GB per chip; with adamw or echo_k=4 it exceeds 16.
FOUR_CHIP_CUTS = (
    ("train.optimizer", "adamw", "sgd",
     "adamw's two fp32 moments would be live twice"),
    ("scenario.echo_k", 4, 2, "each basis entry is one fp32 gradient, "
     "2.4 GB"),
)


def _tpu_devices(count: int):
    """The TPU devices, or SystemExit: this check never runs on a CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, jax found "
                         f"{devices[0].platform}")
    if len(devices) < count:
        raise SystemExit(f"chip_smoke: needs {count} chips, jax found "
                         f"{len(devices)}")
    return devices


def _run_counters(run_dir: str):
    with open(os.path.join(run_dir, "summary.json")) as fh:
        return json.load(fh)["obs"]["counters"]


def _job(name: str, arch: str, smoke: bool, devices: int, **sections):
    from repro import run

    return run.RunConfig(name=name, model=run.ModelSpec(arch=arch,
                                                        smoke=smoke),
                         mesh=run.MeshSpec(devices=devices),
                         obs=run.ObsSpec(tracker="memory"), runs_root=RUNS,
                         **sections)


def phase_train(arch: str = ARCH, smoke: bool = False, batch: int = 8,
                seq: int = 512, steps: int = 6):
    from repro import run
    from repro.configs import get_config, reduced

    # adamw with no warmup moves every weight by about lr on its first
    # steps; at 28 layers lr=1e-3 drives the loss up, 1e-4 does not
    res = run.train(_job(
        "chip-smoke-train", arch, smoke, 1,
        scenario=run.ScenarioSpec(aggregator="mean"),
        train=run.TrainSpec(strategy="replicated", steps=steps,
                            batch=batch, seq=seq, optimizer="adamw",
                            lr=1e-4, log_every=steps)))
    first, final = res.first_loss, res.final_loss
    m = reduced(get_config(arch)) if smoke else get_config(arch)
    print(f"train: {m.name} layers={m.num_layers} d_model={m.d_model} "
          f"heads={m.num_heads}/{m.num_kv_heads} head_dim={m.head_dim} "
          f"d_ff={m.d_ff} vocab={m.vocab_size} smoke={smoke} "
          f"batch={batch}x{seq} steps={res.summary['rounds']} "
          f"loss {first:.4f} -> {final:.4f}", flush=True)
    if not (np.isfinite([first, final]).all() and final < first):
        raise AssertionError(f"train loss {first} -> {final}: not a "
                             f"finite, falling loss")
    return _run_counters(res.run_dir)


def phase_serve(arch: str = ARCH, smoke: bool = False, requests: int = 8,
                prompt_len: int = 128, gen: int = 32):
    from repro import run

    spec = run.ServeSpec(requests=requests, max_batch=4, page_size=16,
                         num_pages=64, max_blocks_per_seq=16,
                         prompt_len=prompt_len, gen=gen, seed=0,
                         log_every=1000)
    res = run.serve(_job("chip-smoke-serve", arch, smoke, 1, serve=spec))
    s = res.summary
    lengths = [len(toks) for toks in res.outputs]
    print(f"serve: {arch} requests={requests} completed={s['completed']} "
          f"prefilled={s['tokens_prefilled']} "
          f"generated={s['tokens_generated']} lanes={spec.max_batch} "
          f"pages={spec.num_pages}x{spec.page_size} "
          f"preemptions={s['preemptions']}", flush=True)
    if s["completed"] != requests or min(lengths) < 1:
        raise AssertionError(f"serve completed {s['completed']} of "
                             f"{requests} requests, lengths {lengths}")
    return _run_counters(res.run_dir)


def phase_paged_attention(arch: str = ARCH, batch: int = 4,
                          num_pages: int = 128, page_size: int = 16,
                          blocks: int = 16):
    """One decode step's attention over a seeded bf16 page pool at the
    model's head shapes: the dispatched kernel against the reference."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.kernels import ops, ref

    m = get_config(arch)
    H, K, hd = m.num_heads, m.num_kv_heads, m.resolved_head_dim
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    q = jax.random.normal(keys[0], (batch, H, hd), jnp.bfloat16)
    kp = jax.random.normal(keys[1], (num_pages, page_size, K, hd),
                           jnp.bfloat16)
    vp = jax.random.normal(keys[2], (num_pages, page_size, K, hd),
                           jnp.bfloat16)
    table = (jax.random.permutation(keys[3], num_pages - 1)[
        :batch * blocks] + 1).reshape(batch, blocks).astype(jnp.int32)
    lengths = jax.random.randint(keys[4], (batch,), 1,
                                 blocks * page_size + 1).astype(jnp.int32)
    lengths = lengths.at[0].set(0)            # an idle lane returns zeros
    got = np.asarray(ops.paged_decode_attention(q, kp, vp, table, lengths),
                     np.float32)
    want = np.asarray(ref.paged_decode_attention_ref(q, kp, vp, table,
                                                     lengths), np.float32)
    err = float(np.max(np.abs(got - want)))
    print(f"paged_attention: B={batch} H={H} K={K} hd={hd} "
          f"pages={num_pages}x{page_size} lengths={lengths.tolist()} "
          f"max|kernel-ref|={err:.3e}", flush=True)
    np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)


def phase_protocol(d: int = 4096, rounds: int = 5):
    import jax
    import jax.numpy as jnp

    from repro.configs.paper_echo_cgc import HEADLINE
    from repro.core import byzantine, costfns, theory
    from repro.core.protocol import run_training
    from repro.core.types import ProtocolConfig, raw_bits

    n, f, sigma = HEADLINE.n, HEADLINE.f, HEADLINE.sigma
    key = jax.random.PRNGKey(0)
    cost = costfns.quadratic(key, d=d, mu=HEADLINE.mu_over_L, L=1.0,
                             sigma=sigma)
    r, eta, _, _, _ = theory.pick_r_eta(n, f, cost.L, cost.mu, sigma)
    byz = jnp.zeros(n, bool).at[:f].set(True)
    trace = run_training(ProtocolConfig(n=n, f=f, r=r, eta=eta), cost,
                         byzantine.ATTACKS["sign_flip"], byz, key,
                         jnp.ones(d) * 2.0, rounds=rounds)
    d2 = np.asarray(trace["dist2"])
    bits = float(np.sum(np.asarray(trace["bits"])))
    p2p = rounds * n * raw_bits(d)
    echoes = np.asarray(trace["n_echo"]).tolist()
    print(f"protocol: n={n} f={f} sign_flip d={d} rounds={rounds} "
          f"||w-w*||^2 {d2[0]:.4f} -> {d2[-1]:.4f} echoes/round={echoes} "
          f"bits {bits:.4g} vs point-to-point {p2p:.4g}", flush=True)
    if not (np.isfinite(d2).all() and d2[-1] < d2[0]):
        raise AssertionError(f"protocol distance {d2[0]} -> {d2[-1]}")


def check_backends(counters) -> None:
    """Every kernel dispatch any phase traced resolved to Pallas."""
    overrides = sorted(k for k in os.environ
                       if k.startswith("REPRO_") and k.endswith("_BACKEND"))
    kernels = {k: v for k, v in counters.items() if k.startswith("kernels.")}
    print(f"backends: {json.dumps(kernels, sort_keys=True)}", flush=True)
    if overrides:
        raise AssertionError(f"backend overrides set: {overrides}")
    jnp_ = sorted(k for k in kernels if k.endswith(".jnp"))
    if jnp_ or not kernels:
        raise AssertionError(f"kernels not on pallas: {jnp_ or 'none ran'}")


def phase_four_chips(arch: str = ARCH, smoke: bool = False,
                     seq: int = 512, steps: int = 3):
    from repro import run

    for field, was, now, why in FOUR_CHIP_CUTS:
        print(f"cut: {field} {was} -> {now} ({why})", flush=True)
    cut = {field: now for field, _, now, _ in FOUR_CHIP_CUTS}
    scenario = run.ScenarioSpec(aggregator="cgc", attack="sign_flip", f=1,
                                n_byz=1, echo_k=cut["scenario.echo_k"])
    # CGC sums the workers' clipped gradients, so sgd steps by about
    # (n - 2) * lr along the mean gradient under one sign-flipper
    train = run.TrainSpec(steps=steps, batch=8, seq=seq,
                          optimizer=cut["train.optimizer"],
                          lr=1e-3, log_every=1)
    for strategy in ("echo_dp", "replicated"):
        # only the summary outlives a run: its final state (weights and
        # basis) would not fit on the chips beside the next run's
        s = run.train(_job(f"chip-smoke-{strategy}", arch, smoke, 4,
                           scenario=scenario,
                           train=dataclasses.replace(train,
                                                     strategy=strategy))
                      ).summary
        line = (f"{s['strategy']}: workers={s['workers']} "
                f"rounds={s['rounds']} loss {s['first_loss']:.4f} -> "
                f"{s['final_loss']:.4f}")
        if "echo_rate" in s:
            line += (f" echo_rate={s['echo_rate']:.3f} bits "
                     f"{s['bits_sent']:.4g} vs all-raw "
                     f"{s['bits_baseline']:.4g} "
                     f"(saved {s['bits_saving'] + 0.0:.3f})")
        print(line, flush=True)
        if s["workers"] != 4 or not np.isfinite(s["final_loss"]):
            raise AssertionError(f"{s['strategy']}: {s}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip echo-DP phase")
    args = ap.parse_args(argv)
    devices = _tpu_devices(args.chips)

    from repro import obs
    from repro.run import facade

    print(f"compile cache: {facade.enable_compile_cache()}", flush=True)
    if args.chips == 4:
        phase_four_chips()
    else:
        counters = {}
        for c in (phase_train(), phase_serve()):
            for k, v in c.items():
                counters[k] = counters.get(k, 0) + v
        tracker = obs.make_tracker("memory")
        with obs.use_tracker(tracker):
            phase_paged_attention()
            phase_protocol()
        for k, v in tracker.snapshot()["counters"].items():
            counters[k] = counters.get(k, 0) + v
        check_backends(counters)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
