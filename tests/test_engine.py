"""Engine + driver-loop tests (repro.launch.engine).

The multi-round echo-DP driver checks run in a subprocess with 8 fake
CPU devices (the session process already initialised jax with a single
device); the single-device Trainer checks (resume equivalence) run
in-process.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np


def _run_subprocess(body: str):
    """Run a snippet under 8 fake CPU devices; raise on failure."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    if r.returncode != 0:
        raise AssertionError(f"subprocess failed:\n{r.stdout}\n{r.stderr}")
    return r.stdout


def test_echo_driver_multi_round_quadratic():
    """The real driver loop on a quadratic cost: (a) fallback rounds are
    bit-for-bit the plain CGC step, (b) the basis rolls exactly on raw
    (fallback) rounds — successful echo rounds reuse it unchanged,
    mirroring the paper where only RAW broadcasts enter the reference
    set R — and (c) cumulative bit accounting lands well below the
    all-raw baseline."""
    _run_subprocess("""
        import copy
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import costfns
        from repro.core.types import echo_bits, raw_bits
        from repro.launch.engine import (EchoDpStrategy, ReplicatedStrategy,
                                         Trainer, TrainerConfig,
                                         TrainSettings)
        from repro.optim import sgd

        n, d, K, rounds = 8, 128, 4, 16
        shocks = (5, 9)        # rounds whose worker noise breaks Eq. 7
        cost = costfns.quadratic(jax.random.PRNGKey(0), d=d, mu=0.5, L=1.0,
                                 sigma=0.0)

        def loss_fn(values, batch):
            w = values["w"]
            return cost.value(w) + w @ jnp.mean(batch["eps"], 0), {}

        def batch_for(step):
            scale = 10.0 if step in shocks else 1e-4
            key = jax.random.fold_in(jax.random.PRNGKey(7), step)
            return {"eps": scale * jax.random.normal(key, (n, d))}

        mesh = jax.make_mesh((8,), ("data",))
        opt = sgd(0.02)
        settings = TrainSettings(aggregator="cgc", f=1, echo_k=K,
                                 echo_r=0.9)
        tr = Trainer(EchoDpStrategy(loss_fn=loss_fn), None, opt, settings,
                     mesh, n, TrainerConfig(log_every=100))
        values = {"w": jnp.ones((d,)) * 2.0}
        state = tr.init_state(values)

        # an independently built plain CGC step (what the driver must
        # fall back to, bit for bit)
        plain = jax.jit(ReplicatedStrategy(loss_fn=loss_fn).build(
            None, opt, type(settings)(aggregator="cgc", f=1,
                                      return_aggregate=True),
            mesh, n).fn)

        recs = []
        with jax.set_mesh(mesh):
            for s in range(rounds):
                batch = batch_for(s)
                # the fallback step donates (values, opt_state), so the
                # replay oracle below needs its own copies of the
                # pre-round buffers
                pre = type(state)(jax.tree.map(jnp.copy, state.values),
                                  jax.tree.map(jnp.copy, state.opt_state),
                                  state.step, state.basis)
                state, rec = tr.run_round(state, batch)
                recs.append(rec)
                if not rec["all_echo"]:
                    # (a) bit-for-bit identical to the plain CGC step
                    v2, o2, m2, agg2 = plain(pre.values, pre.opt_state,
                                             batch, jnp.asarray(pre.step))
                    for a, b in zip(jax.tree.leaves(state.values),
                                    jax.tree.leaves(v2)):
                        assert np.array_equal(np.asarray(a), np.asarray(b))
                    # ...and the rolled-in basis entry IS that aggregate
                    for a, b in zip(jax.tree.leaves(state.basis[-1]),
                                    jax.tree.leaves(agg2)):
                        assert np.array_equal(np.asarray(a), np.asarray(b))
                    assert rec["basis_rolled"]
                else:
                    # (b) successful echo rounds leave the basis alone
                    assert state.basis is pre.basis
                    assert not rec["basis_rolled"]

        flags = [r["all_echo"] for r in recs]
        assert not flags[0]                      # zero basis: raw round
        for s in shocks:
            assert not flags[s], flags           # shocks force fallback
        assert sum(flags) >= rounds - 5, flags   # fast path dominates
        # (c) cumulative bits far below the all-raw baseline
        assert tr.bits_baseline == rounds * n * raw_bits(d)
        n_raw = rounds - sum(flags)
        want = rounds * n * int(echo_bits(n, K)) + n_raw * n * raw_bits(d)
        assert tr.bits_sent == want, (tr.bits_sent, want)
        assert tr.bits_sent < 0.5 * tr.bits_baseline
        losses = [r["loss"] for r in recs]
        assert np.isfinite(losses).all()
        assert min(losses) < losses[0]

        # checkpoint round-trips the full echo state (incl. the basis)
        import tempfile
        tmp = tempfile.mkdtemp()
        tr.config = type(tr.config)(ckpt_dir=tmp)
        tr.save(state)
        back = tr.restore(tr.init_state(values))
        assert back.step == state.step
        for a, b in zip(jax.tree.leaves(back.basis),
                        jax.tree.leaves(state.basis)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        print("OK", flags)
    """)


def test_trainer_resume_equivalence():
    """fit -> checkpoint -> resume == uninterrupted run (values and
    optimizer moments restored, not just weights)."""
    from repro.launch.engine import (ReplicatedStrategy, Trainer,
                                     TrainerConfig, TrainSettings)
    from repro.optim import adamw

    d = 16

    def loss_fn(values, batch):
        w = values["w"]
        return 0.5 * jnp.sum((w - 1.0) ** 2) + w @ jnp.mean(
            batch["eps"], 0), {}

    def batch_for(step):
        key = jax.random.fold_in(jax.random.PRNGKey(3), step)
        return {"eps": 0.05 * jax.random.normal(key, (4, d))}

    values = {"w": jnp.zeros((d,))}
    settings = TrainSettings(aggregator="mean")

    def make(cfg):
        return Trainer(ReplicatedStrategy(loss_fn=loss_fn), None,
                       adamw(0.1), settings, None, 4, cfg,
                       printer=lambda s: None)

    trA = make(TrainerConfig())
    sA = trA.init_state(values)
    for s in range(8):
        sA, _ = trA.run_round(sA, batch_for(s))

    import tempfile
    tmp = tempfile.mkdtemp()
    trB = make(TrainerConfig(ckpt_dir=tmp))
    sB = trB.init_state(values)
    for s in range(4):
        sB, _ = trB.run_round(sB, batch_for(s))
    trB.save(sB)

    trC = make(TrainerConfig(ckpt_dir=tmp, resume=True))
    sC = trC.init_state(values)
    assert sC.step == 4
    for s in range(4, 8):
        sC, _ = trC.run_round(sC, batch_for(s))

    np.testing.assert_allclose(np.asarray(sA.values["w"]),
                               np.asarray(sC.values["w"]), rtol=1e-6)
    for a, c in zip(jax.tree.leaves(sA.opt_state),
                    jax.tree.leaves(sC.opt_state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-6)


def test_trainer_async_checkpoint_resume_equivalence():
    """fit() checkpoints on the background writer (ckpt_every saves never
    block the driver); after close() the snapshot is durable and a
    resumed run continues bit-identically to an uninterrupted one."""
    import itertools
    import tempfile

    from repro.launch.engine import (ReplicatedStrategy, Trainer,
                                     TrainerConfig, TrainSettings)
    from repro.optim import adamw

    d = 16

    def loss_fn(values, batch):
        w = values["w"]
        return 0.5 * jnp.sum((w - 1.0) ** 2) + w @ jnp.mean(
            batch["eps"], 0), {}

    def batches(start=0):
        for s in itertools.count(start):
            key = jax.random.fold_in(jax.random.PRNGKey(3), s)
            yield {"eps": 0.05 * jax.random.normal(key, (4, d))}

    values = {"w": jnp.zeros((d,))}
    settings = TrainSettings(aggregator="mean")

    def make(cfg):
        return Trainer(ReplicatedStrategy(loss_fn=loss_fn), None,
                       adamw(0.1), settings, None, 4, cfg,
                       printer=lambda s: None)

    trA = make(TrainerConfig())
    sA, _ = trA.fit(trA.init_state(values), batches(), 8)

    tmp = tempfile.mkdtemp()
    trB = make(TrainerConfig(ckpt_dir=tmp, ckpt_every=2))
    sB, _ = trB.fit(trB.init_state(values), batches(), 5)
    trB.close()                        # flush-on-close makes saves durable

    trC = make(TrainerConfig(ckpt_dir=tmp, resume=True))
    sC = trC.init_state(values)
    assert sC.step == 5                # resumed from the async final save
    sC, _ = trC.fit(sC, batches(start=5), 8)

    np.testing.assert_array_equal(np.asarray(sA.values["w"]),
                                  np.asarray(sC.values["w"]))
    for a, c in zip(jax.tree.leaves(sA.opt_state),
                    jax.tree.leaves(sC.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_strategies_registry_and_bundle_contract():
    """All strategies build through the one engine skeleton; the
    replicated no-mesh bundle keeps the (values, opt_state, metrics)
    contract."""
    from repro.configs import get_config, reduced
    from repro.data import train_inputs
    from repro.launch.engine import STRATEGIES, Trainer, TrainerConfig, \
        TrainSettings
    from repro.models import model as M
    from repro.models.nn import split_params
    from repro.optim import sgd

    assert set(STRATEGIES) == {"replicated", "fsdp", "echo_dp"}
    cfg = reduced(get_config("qwen3-0.6b"), layers=2, d_model=128)
    opt = sgd(0.05)

    # FSDP has no replicated aggregate to emit — build must refuse
    import pytest
    from jax.sharding import AbstractMesh
    with pytest.raises(ValueError, match="return_aggregate"):
        STRATEGIES["fsdp"]().build(
            cfg, opt, TrainSettings(fsdp=True, return_aggregate=True),
            AbstractMesh((8,), ("data",)), 8)
    b = STRATEGIES["replicated"]().build(cfg, opt, TrainSettings(), None, 4)
    assert not b.needs_basis and b.value_shardings is None
    values, _ = split_params(M.init_params(cfg, jax.random.PRNGKey(0)))
    batch = train_inputs(jax.random.PRNGKey(1), cfg, 4, 16)
    v, o, m = jax.jit(b.fn)(values, opt.init(values), batch,
                            jnp.asarray(0))
    assert np.isfinite(float(m["loss"]))

    tr = Trainer("replicated", cfg, opt, TrainSettings(), None, 4,
                 TrainerConfig(), printer=lambda s: None)
    state = tr.init_state(values)
    state, rec = tr.run_round(state, batch)
    assert state.step == 1 and rec["bits"] == rec["bits_baseline_cumulative"]


def test_metrics_sink_async_flush(tmp_path):
    """The jsonl sink buffers writes on a background thread: emit() never
    blocks on file I/O, flush() is a barrier, close() drains everything."""
    import json

    from repro.launch.engine import MetricsSink

    path = tmp_path / "metrics.jsonl"
    lines_printed = []
    sink = MetricsSink(str(path), log_every=50,
                       printer=lines_printed.append)
    for i in range(200):
        sink.emit({"step": i, "loss": float(i)})
    sink.flush()
    assert len(path.read_text().splitlines()) == 200
    sink.emit({"step": 200, "loss": 0.5})
    sink.close()
    records = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(records) == 201 and records[-1]["step"] == 200
    assert lines_printed and lines_printed[0].startswith("step ")
    sink.close()                       # idempotent
