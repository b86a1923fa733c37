"""Distribution layer tests.

Sharding-rule logic runs on AbstractMesh (no devices needed); collective
behaviour runs in subprocesses with XLA_FLAGS forcing 8 host devices (the
session process already initialised jax with a single CPU device).
"""
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.dist import DEFAULT_RULES, EP_RULES, spec_for

MESH1 = AbstractMesh((16, 16), ("data", "model"))
MESH2 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_rules_basic():
    # mlp dim -> model axis
    assert spec_for((4096, 13440), ("embed", "mlp"), MESH1) == \
        P(None, "model")
    # batch -> (pod, data) on the multi-pod mesh
    assert spec_for((256, 4096), ("batch", None), MESH2) == \
        P(("pod", "data"), None)
    assert spec_for((256, 4096), ("batch", None), MESH1) == P("data", None)


def test_rules_divisibility_fallback():
    # kv_heads=8 not divisible by model=16 -> replicated, kv_seq takes model
    spec = spec_for((128, 32768, 8, 128),
                    ("batch", "kv_seq", "kv_heads", None), MESH1)
    assert spec == P("data", "model", None, None)
    # kv_heads=32 divisible -> heads win over kv_seq (priority)
    spec = spec_for((128, 32768, 32, 128),
                    ("batch", "kv_seq", "kv_heads", None), MESH1)
    assert spec == P("data", None, "model", None)


def test_rules_no_axis_reuse():
    # batch=1 unshardable; kv_seq may then use (data, model) jointly
    spec = spec_for((1, 524288, 8, 128),
                    ("batch", "kv_seq", "kv_heads", None), MESH1)
    assert spec == P(None, ("data", "model"), None, None)


def test_ep_rules_shard_experts():
    spec = spec_for((160, 5120, 1536), ("expert", "embed", "mlp"), MESH1,
                    EP_RULES)
    assert spec == P("model", None, None)
    spec = spec_for((160, 5120, 1536), ("expert", "embed", "mlp"), MESH1,
                    DEFAULT_RULES)
    assert spec == P(None, None, "model")


def test_vocab_padding_divisible():
    from repro.configs import ARCH_IDS, get_config
    for a in ARCH_IDS:
        cfg = get_config(a)
        assert cfg.padded_vocab % 16 == 0, a
        assert cfg.padded_vocab >= cfg.vocab_size


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


def test_cgc_aggregation_collective():
    """CGC over the data axis neutralises a large-norm Byzantine worker."""
    _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.dist.collectives import (aggregate_pytree_cgc,
                                            aggregate_pytree_mean,
                                            inject_byzantine, worker_index)

        mesh = jax.make_mesh((8,), ("data",))

        def step(x):
            wid = worker_index(("data",))
            g = {"w": x * 0 + 1.0}                    # honest grad = ones
            g = inject_byzantine(g, wid, 1, "large_norm", scale=100.0)
            agg, diags = aggregate_pytree_cgc(g, ("data",), f=1)
            agg_mean, _ = aggregate_pytree_mean(g, ("data",))
            return agg["w"], agg_mean["w"]

        sm = jax.shard_map(step, mesh=mesh, in_specs=P("data"),
                           out_specs=(P(), P()), check_vma=False)
        x = jnp.zeros((8,))
        cgc, mean = jax.jit(sm)(x)
        # mean is destroyed by the -100x worker; CGC bounds it near 1
        assert abs(float(mean[0]) - 1.0) > 5.0, float(mean[0])
        err = abs(float(cgc[0]) - 1.0)
        assert err < 0.5, float(cgc[0])
        print("OK")
    """)


def test_agg_fns_cgc_matches_gathered_reference():
    """AGG_FNS["cgc"] inside shard_map == core.aggregators.cgc_sum on the
    gathered (n, d) table (same filtered-sum convention, paper line 44)."""
    _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.aggregators import cgc_sum
        from repro.dist import AGG_FNS
        from repro.dist.collectives import worker_index

        n, d, f = 8, 96, 2
        table = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        table = table * (1.0 + 3.0 * jnp.arange(n)[:, None])  # norm spread

        def step(rows):
            g = {"w": rows[0, :64], "b": rows[0, 64:]}   # pytree split of g
            agg, diags = AGG_FNS["cgc"](g, ("data",), f)
            return jnp.concatenate([agg["w"], agg["b"]])

        mesh = jax.make_mesh((n,), ("data",))
        sm = jax.shard_map(step, mesh=mesh, in_specs=P("data", None),
                           out_specs=P(), check_vma=False)
        got = jax.jit(sm)(table)
        want = cgc_sum(table, f)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        print("OK")
    """)


def test_sharded_train_step_runs():
    """Full CGC train step on a (4, 2) mesh: loss finite, params move."""
    _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.data import train_inputs
        from repro.launch.train import TrainSettings, make_train_step
        from repro.models import model as M
        from repro.models.nn import split_params
        from repro.optim import adamw

        cfg = reduced(get_config("qwen3-0.6b"))
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        opt = adamw(1e-3)
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        values, _ = split_params(params)
        state = opt.init(values)
        step_fn, ctx = make_train_step(
            cfg, opt, TrainSettings(aggregator="cgc", f=1, n_byz=1),
            mesh, global_batch=8)
        batch = train_inputs(jax.random.PRNGKey(1), cfg, 8, 32)
        with jax.set_mesh(mesh):
            v2, s2, metrics = jax.jit(step_fn)(values, state, batch,
                                               jnp.asarray(0))
        assert np.isfinite(float(metrics["loss"]))
        moved = sum(float(jnp.sum(jnp.abs(a - b)))
                    for a, b in zip(jax.tree.leaves(values),
                                    jax.tree.leaves(v2)))
        assert moved > 0
        print("OK", float(metrics["loss"]))
    """)


def test_moe_sharded_matches_local():
    """shard_map MoE (tp mode) == single-device moe_local."""
    _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.dist import make_shard_ctx
        from repro.models import model as M, moe
        from repro.models.nn import split_params

        cfg = reduced(get_config("qwen3-moe-30b-a3b"))
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        values, _ = split_params(params)
        p = jax.tree.map(lambda a: a[0], values["layers"])["moe"]
        x = jax.random.normal(jax.random.PRNGKey(2), (8, 16, cfg.d_model))

        y_local, st_local = moe.moe_forward(p, cfg, x, None)

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        ctx = make_shard_ctx(mesh, 8)
        with jax.set_mesh(mesh):
            y_sh, st_sh = jax.jit(
                lambda p, x: moe.moe_forward(p, cfg, x, ctx))(p, x)
        np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_sh),
                                   rtol=2e-3, atol=2e-3)
        # aux loss: per-shard f_e*P_e averaged != global f_e*P_e exactly
        # (standard DP behaviour) — require agreement to a few percent only.
        np.testing.assert_allclose(float(st_local.aux_loss),
                                   float(st_sh.aux_loss), rtol=5e-2)
        print("OK")
    """)


def test_expert_parallel_matches_local():
    """EP dispatch == local MoE oracle (both dropless)."""
    _run_subprocess("""
        import dataclasses as dc
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.dist import make_shard_ctx
        from repro.models import model as M, moe
        from repro.models.nn import split_params

        cfg = reduced(get_config("qwen3-moe-30b-a3b"))
        cfg = dc.replace(cfg, num_experts=4, top_k=2)
        values, _ = split_params(M.init_params(cfg, jax.random.PRNGKey(0)))
        p = jax.tree.map(lambda a: a[0], values["layers"])["moe"]
        x = jax.random.normal(jax.random.PRNGKey(2), (8, 16, cfg.d_model))
        y_local, _ = moe.moe_forward(p, cfg, x, None)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        ctx = dc.replace(make_shard_ctx(mesh, 8), moe_impl="ep")
        with jax.set_mesh(mesh):
            y_ep, st = jax.jit(
                lambda p, x: moe.moe_forward(p, cfg, x, ctx))(p, x)
        err = float(jnp.max(jnp.abs(y_local - y_ep)))
        assert err < 2e-3, err
        assert float(st.dropped_frac) == 0.0
        print("OK", err)
    """)


def test_fsdp_matches_replicated_trainer():
    """FSDP + blockwise-CGC step against the replicated CGC step and a
    plain blockwise-CGC reference.

    Distinct worker rows: the FSDP step equals SGD on a jnp blockwise
    CGC of the same per-worker gradients (one clip per block: the top-
    level sharded leaves, each layer's sharded leaves, and the
    replicated rest), and that clip bites in every block. Identical
    worker rows: every block norm ties across workers, so blockwise
    clipping is the whole-gradient clipping and the FSDP step equals
    the replicated trainer's."""
    _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        import repro.dist.fsdp as F
        F.MIN_FSDP_ELEMS = 1 << 10
        from repro.configs import get_config, reduced
        from repro.core.cgc import cgc_scales
        from repro.data import make_batch_iterator
        from repro.launch.train import (TrainSettings, make_train_step,
                                        make_fsdp_train_step)
        from repro.models import model as M
        from repro.models.nn import split_params
        from repro.optim import sgd

        cfg = reduced(get_config("qwen3-0.6b"), layers=2, d_model=256)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        lr, n, f = 0.05, 4, 1
        opt = sgd(lr)
        values, _ = split_params(M.init_params(cfg, jax.random.PRNGKey(0)))
        state = opt.init(values)
        st = TrainSettings(aggregator="cgc", f=f, fsdp=True)
        fsdp_step, ctx, (vshard, plan) = make_fsdp_train_step(
            cfg, opt, st, mesh, 8)
        rep_step, _ = make_train_step(
            cfg, opt, TrainSettings(aggregator="cgc", f=f), mesh, 8)

        def fsdp(batch):
            with jax.set_mesh(mesh):
                vP = jax.device_put(values, vshard)
                return jax.jit(fsdp_step)(vP, state, batch, jnp.asarray(0))

        def max_diff(xs, ys):
            return max(float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(xs), jax.tree.leaves(ys)))

        # -- distinct rows: FSDP == SGD on a jnp blockwise CGC ----------
        batch = next(make_batch_iterator(cfg, 8, 32, seed=0))
        v1, _, m1 = fsdp(batch)
        paths, treedef = jax.tree_util.tree_flatten_with_path(values)
        dims = jax.tree.flatten(plan, is_leaf=lambda x: x is None)[0]
        assert len(paths) == len(dims)
        layered = [p[0].key == "layers" for p, _ in paths]
        grads = [jax.tree.leaves(jax.grad(
            lambda v: M.train_loss(v, cfg, jax.tree.map(
                lambda x: x[2 * w:2 * w + 2], batch))[0])(values))
            for w in range(n)]
        G = [jnp.stack([g[i] for g in grads]).astype(jnp.float32)
             for i in range(len(dims))]          # leaf i: (n, *shape)
        top = [i for i, d in enumerate(dims) if d is not None
               and not layered[i]]
        lay = [i for i, d in enumerate(dims) if d is not None and layered[i]]
        rest = [i for i, d in enumerate(dims) if d is None]
        assert top and lay and rest
        agg = [None] * len(dims)
        clipped = []
        for block in (top, rest):                # one scale per worker
            sq = sum(jnp.sum(G[i] ** 2, axis=tuple(range(1, G[i].ndim)))
                     for i in block)
            s = cgc_scales(jnp.sqrt(sq), f)
            clipped.append(float(jnp.min(s)))
            for i in block:
                agg[i] = jnp.tensordot(s, G[i], axes=1)
        sq = sum(jnp.sum(G[i] ** 2, axis=tuple(range(2, G[i].ndim)))
                 for i in lay)                   # (n, layers)
        s = jax.vmap(lambda col: cgc_scales(col, f), 1, 1)(jnp.sqrt(sq))
        clipped += [float(v) for v in jnp.min(s, axis=0)]
        for i in lay:
            agg[i] = jnp.einsum("wl,wl...->l...", s, G[i])
        assert max(clipped) < 0.999, clipped     # the clip bites
        want = jax.tree.unflatten(treedef, [
            (v.astype(jnp.float32) - lr * a).astype(v.dtype)
            for v, a in zip(jax.tree.leaves(values), agg)])
        d_ref = max_diff(v1, want)
        assert d_ref < 1e-5, d_ref

        # -- identical rows: FSDP == the replicated trainer -------------
        same = jax.tree.map(
            lambda x: jnp.tile(x[:2], (n,) + (1,) * (x.ndim - 1)), batch)
        v1, _, m1 = fsdp(same)
        with jax.set_mesh(mesh):
            v2, _, m2 = jax.jit(rep_step)(values, state, same,
                                          jnp.asarray(0))
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-5
        d_rep = max_diff(v1, v2)
        assert d_rep < 1e-6, d_rep
        print("OK", d_ref, d_rep, clipped)
    """)


def test_echo_dp_optimistic_training():
    """Echo-compressed DP aggregation: fast path engages, loss converges."""
    _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.data import make_batch_iterator
        from repro.launch.train import (TrainSettings, make_train_step,
                                        make_echo_train_step)
        from repro.models import model as M
        from repro.models.nn import split_params
        from repro.optim import sgd
        from repro.dist.echo_dp import init_basis, roll_basis

        cfg = reduced(get_config("xlstm-125m"), layers=2, d_model=128)
        mesh = jax.make_mesh((8,), ("data",))
        opt = sgd(0.02)
        values, _ = split_params(M.init_params(cfg, jax.random.PRNGKey(0)))
        state = opt.init(values)
        K = 4
        st = TrainSettings(aggregator="cgc", f=1, echo_k=K, echo_r=0.98,
                           return_aggregate=True)
        echo_step, _ = make_echo_train_step(cfg, opt, st, mesh, 32)
        full_step, _ = make_train_step(cfg, opt, st, mesh, 32)
        ej, fj = jax.jit(echo_step), jax.jit(full_step)
        basis = init_basis(values, K)
        it = make_batch_iterator(cfg, 32, 128, seed=0)
        n_fast, losses = 0, []
        with jax.set_mesh(mesh):
            for s in range(16):
                b = next(it)
                v2, s2, m, agg = ej(values, state, b, jnp.asarray(s), basis)
                if bool(m["all_echo"]):
                    values, state = v2, s2
                    n_fast += 1
                else:
                    values, state, m, agg = fj(values, state, b,
                                               jnp.asarray(s))
                basis = roll_basis(basis, agg)
                losses.append(float(m["loss"]))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]
        assert n_fast >= 4, n_fast      # fast path engages after warm-up
        print("OK fast:", n_fast, "loss:", losses[0], "->", losses[-1])
    """)


def test_byzantine_resistance_end_to_end():
    """CGC training under large-norm attackers beats mean aggregation
    (loss-wise). Both sides take the same step size: CGC aggregates the
    paper's filtered sum over n = 8 workers, so its learning rate is the
    mean's divided by n."""
    _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.data import make_batch_iterator
        from repro.launch.train import TrainSettings, make_train_step
        from repro.models import model as M
        from repro.models.nn import split_params
        from repro.optim import sgd

        cfg = reduced(get_config("xlstm-125m"), layers=2, d_model=128)
        mesh = jax.make_mesh((8,), ("data",))

        def run(aggregator, f, lr):
            opt = sgd(lr)
            params = M.init_params(cfg, jax.random.PRNGKey(0))
            values, _ = split_params(params)
            state = opt.init(values)
            fn, _ = make_train_step(
                cfg, opt,
                TrainSettings(aggregator=aggregator, f=f, n_byz=2,
                              byz_mode="large_norm"),
                mesh, global_batch=8)
            it = make_batch_iterator(cfg, 8, 32, seed=3)
            with jax.set_mesh(mesh):
                jf = jax.jit(fn)
                for s in range(10):
                    values, state, m = jf(values, state, next(it),
                                          jnp.asarray(s))
            return float(m["loss"])

        loss_cgc = run("cgc", 2, 0.05 / 8)
        loss_mean = run("mean", 0, 0.05)
        assert np.isfinite(loss_cgc)
        assert loss_cgc < loss_mean or not np.isfinite(loss_mean), (
            loss_cgc, loss_mean)
        print("OK", loss_cgc, loss_mean)
    """)
