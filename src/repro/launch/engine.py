"""Strategy-based training engine + the real driver loop (DESIGN.md §6).

``launch/train.py`` used to carry three ~120-line ``make_*_train_step``
builders whose shard_map plumbing, microbatch accumulation, batch specs
and metrics handling were copy-pasted. This module factors that stack:

    TrainStrategy       protocol: builds specs + the worker_fn body
    ReplicatedStrategy  params replicated over the worker axes, AGG_FNS
                        (CGC / Krum / median / trimmed-mean) aggregation
    FsdpStrategy        params + opt state sharded over the worker axes,
                        blockwise-CGC reduce-scatter in the gather VJP
    EchoDpStrategy      coefficient-space optimistic aggregation (the
                        paper's echo idea as a DP fast path)
    Trainer             the driver: echo-DP optimistic rounds with
                        ``all_echo`` fallback to the exact CGC step,
                        basis bookkeeping, checkpoint/resume of
                        (values, opt_state, step, basis), a pluggable
                        metrics sink (jsonl + stdout), and per-round bit
                        accounting (``core.types.echo_bits``/``raw_bits``)
                        so the paper's communication-savings curve falls
                        out of a training run.

All strategies share ONE shard_map wrapper, ONE microbatch/grad-
accumulation path, ONE batch-spec helper and ONE metrics contract:
``step(values, opt_state, batch, step[, basis]) -> (values, opt_state,
metrics[, aggregate])`` where ``metrics`` always contains ``loss`` plus
per-strategy diagnostics (``all_echo``, ``cgc_threshold``, ...).

The CLI (``python -m repro.launch.train --strategy {replicated,fsdp,
echo_dp}``) is a thin shell over :class:`Trainer`.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import (Any, Callable, Dict, Iterator, List, Optional, Protocol,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import checkpoint as ckpt_lib
from repro import obs
from repro.comm import CommConfig, CommLedger, DEFAULT_COMM, raw_round_bits
from repro.obs.writer import AsyncLineWriter
from repro.run.registry import TRAIN_STRATEGIES
from repro.dist import (AGG_FNS, ShardCtx, inject_byzantine, make_shard_ctx,
                        tree_shardings)
from repro.dist.echo_dp import (basis_gram, echo_dp_aggregate, init_basis,
                                roll_basis, round_comm_bits)
from repro.models import model as M
from repro.obs.scopes import TRAIN_AGGREGATE, TRAIN_GRADS, TRAIN_OPTIMIZER
from repro.optim import Optimizer, clip_by_global_norm

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class TrainSettings:
    aggregator: str = "cgc"        # mean | cgc | trimmed_mean | ...
    f: int = 0                     # CGC clip count (max Byzantine workers)
    n_byz: int = 0                 # simulated Byzantine workers (testing)
    byz_mode: str = "sign_flip"
    microbatches: int = 1
    clip_norm: float = 0.0         # 0 = off
    moe_impl: str = "tp"
    return_aggregate: bool = False  # emit the aggregated grads (echo basis)
    echo_k: int = 4                # echo-DP: reference basis size
    echo_r: float = 0.5            # echo-DP: deviation ratio (Eq. 7)
    fsdp: bool = False             # shard params+opt over the data axes
                                   # (blockwise CGC in the gather VJP)
    remat: str = "full"            # "full" | "save_psum" (§Perf HC2)
    # Communication setup (repro.comm): wire codec + broadcast channel.
    # None = the paper's ideal fp32 comm (bitwise the pre-comm engine).
    comm: Optional[CommConfig] = None
    # Closed-loop control plane (repro.comm.policy, DESIGN.md §13):
    # ``policy`` is a resolved CommPolicy instance (None = no controller,
    # bitwise the pre-policy engine; a static policy only emits events).
    # ``ef`` threads per-worker error-feedback residuals through the
    # echo-DP coefficient all-gather. ``dynamic_r`` is engine-internal:
    # the Trainer sets it on the per-codec step bundles it builds for a
    # dynamic policy, so the step takes Eq. 7's r as a *traced* scalar
    # (policy retunes it per round with zero recompiles).
    policy: Optional[Any] = None
    ef: bool = False
    dynamic_r: bool = False


# ---------------------------------------------------------------------------
# Shared plumbing: microbatching, batch specs, shard_map wrapper
# ---------------------------------------------------------------------------


def _slice_batch(batch: Dict[str, jax.Array], i, n_micro: int):
    """The i-th of n_micro slices (mrope_positions has batch at dim 1)."""
    out = {}
    for k, x in batch.items():
        dim = 1 if k == "mrope_positions" else 0
        mb = x.shape[dim] // n_micro
        out[k] = jax.lax.dynamic_slice_in_dim(x, i * mb, mb, dim)
    return out


def microbatched_grads(loss_fn, values, batch, n_micro: int):
    """Gradient accumulation over n_micro slices of the local batch.

    ``loss_fn(values, batch) -> (loss, metrics)``; the metrics zeros are
    derived with eval_shape, so any metrics pytree works (one contract
    for LM losses and the scalar cost functions used in tests).
    """
    if n_micro <= 1:
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(values, batch)
        return loss, metrics, grads

    def body(carry, i):
        g_acc, l_acc, m_acc = carry
        (loss, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(
            values, _slice_batch(batch, i, n_micro))
        g_acc = jax.tree.map(jnp.add, g_acc, g)
        m_acc = jax.tree.map(jnp.add, m_acc, metrics)
        return (g_acc, l_acc + loss, m_acc), None

    zeros_g = jax.tree.map(lambda v: jnp.zeros(v.shape, F32), values)
    m_abs = jax.eval_shape(loss_fn, values, _slice_batch(batch, 0, n_micro))
    zero_m = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), m_abs[1])
    (g, loss, metrics), _ = jax.lax.scan(
        body, (zeros_g, jnp.zeros((), F32), zero_m), jnp.arange(n_micro))
    inv = 1.0 / n_micro
    return (loss * inv,
            jax.tree.map(lambda m: m * inv, metrics),
            jax.tree.map(lambda x: x * inv, g))


def batch_partition_spec(name: str, data_axes: Sequence[str]) -> P:
    """Spec of one batch entry: sharded over the worker axes on dim 0
    (dim 1 for mrope_positions)."""
    axes = tuple(data_axes)
    bspec = axes if len(axes) > 1 else axes[0]
    return P(None, bspec) if name == "mrope_positions" else P(bspec)


def batch_specs(batch: Dict[str, Any], data_axes: Sequence[str]
                ) -> Dict[str, P]:
    return {k: batch_partition_spec(k, data_axes) for k in batch}


def replicated_specs(tree) -> Any:
    return jax.tree.map(lambda _: P(), tree)


def mirror_opt_specs(vspecs, opt_state) -> Any:
    """Mirror parameter specs onto mirroring optimizer-state subtrees.

    Optimizer states that stack N param-shaped trees (Adam's mu/nu) get
    the param specs repeated; anything else is replicated.
    """
    leaves, treedef = jax.tree.flatten(opt_state)
    vleaves = jax.tree.leaves(vspecs)
    if vleaves and len(leaves) % len(vleaves) == 0:
        reps = len(leaves) // len(vleaves)
        return jax.tree.unflatten(treedef, vleaves * reps)
    return replicated_specs(opt_state)


# ---------------------------------------------------------------------------
# Strategy protocol + shared build skeleton
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StepBundle:
    """A built train step and everything the driver needs to run it."""

    name: str
    fn: Callable                    # (values, opt_state, batch, step[, basis])
    ctx: ShardCtx
    settings: TrainSettings
    needs_basis: bool = False       # fn takes a trailing basis list
    returns_aggregate: bool = False  # fn emits the aggregate pytree
    value_shardings: Any = None     # placement shardings (FSDP) or None
    plan: Any = None                # FSDP shard plan or None


class TrainStrategy(Protocol):
    """Builds the per-worker step body + its shard_map specs."""

    name: str
    needs_basis: bool

    def build(self, cfg, opt: Optimizer, settings: TrainSettings, mesh,
              global_batch: int) -> StepBundle: ...


class _StrategyBase:
    """Template build(): one worker body, one spec path, one wrapper.

    Subclasses override the hooks (validate / prepare / make_loss_fn /
    aggregate / clip / value_specs / opt_specs); the shard_map wrapping,
    microbatching, Byzantine injection, loss/metrics pmean, gradient
    clipping and optimizer update live here exactly once.

    ``loss_fn`` (constructor) overrides the LM loss with any
    ``(values, batch) -> (loss, metrics)`` callable — the driver tests
    run the full engine on quadratic costs this way.
    """

    name = "base"
    needs_basis = False

    def __init__(self, loss_fn: Optional[Callable] = None):
        self.loss_override = loss_fn

    # --- hooks -------------------------------------------------------

    def validate(self, settings: TrainSettings, ctx: ShardCtx, mesh):
        pass

    def prepare(self, cfg, opt, settings, mesh, ctx) -> Dict[str, Any]:
        return {}

    def make_loss_fn(self, cfg, settings, mesh, ctx, env) -> Callable:
        raise NotImplementedError

    def aggregate(self, env, grads, settings, data_axes, extra
                  ) -> Tuple[Any, Dict[str, jax.Array]]:
        raise NotImplementedError

    def clip(self, env, grads, settings, data_axes):
        return clip_by_global_norm(grads, settings.clip_norm)

    def value_specs(self, env, values):
        return replicated_specs(values)

    def opt_specs(self, env, opt_state, vspecs):
        return replicated_specs(opt_state)

    # --- template ----------------------------------------------------

    def build(self, cfg, opt: Optimizer, settings: TrainSettings, mesh,
              global_batch: int) -> StepBundle:
        ctx = make_shard_ctx(mesh, global_batch, settings.moe_impl)
        data_axes = ctx.batch_axes
        self.validate(settings, ctx, mesh)
        env = self.prepare(cfg, opt, settings, mesh, ctx)
        loss_fn = self.loss_override or self.make_loss_fn(
            cfg, settings, mesh, ctx, env)
        ret_agg = self.needs_basis or settings.return_aggregate

        def worker_fn(values, opt_state, batch, step, *extra):
            with jax.named_scope(TRAIN_GRADS):
                loss, metrics, grads = microbatched_grads(
                    loss_fn, values, batch, settings.microbatches)
            if settings.n_byz and data_axes:
                from repro.dist.collectives import worker_index
                grads = inject_byzantine(grads, worker_index(data_axes),
                                         settings.n_byz, settings.byz_mode)
            with jax.named_scope(TRAIN_AGGREGATE):
                agg, diags = self.aggregate(env, grads, settings,
                                            data_axes, extra)
                if data_axes:
                    loss = jax.lax.pmean(loss, data_axes)
                    metrics = jax.tree.map(
                        lambda m: jax.lax.pmean(m, data_axes), metrics)
                if settings.clip_norm:
                    agg, gnorm = self.clip(env, agg, settings, data_axes)
                    diags = dict(diags, grad_global_norm=gnorm)
            with jax.named_scope(TRAIN_OPTIMIZER):
                updates, opt_state = opt.update(agg, opt_state, values,
                                                step)
                values = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                                      values, updates)
            metrics = dict(metrics, loss=loss, **diags)
            if ret_agg:
                return values, opt_state, metrics, agg
            return values, opt_state, metrics

        if mesh is None or not data_axes:
            return StepBundle(self.name, worker_fn, ctx, settings,
                              returns_aggregate=ret_agg)

        # The TPU compiler cannot partition a Pallas kernel (the norm and
        # CGC passes) over an auto axis, even one of size 1. A size-1
        # axis partitions nothing, so it is made manual with the data
        # axes, unless a nested EP region shards over it.
        manual = set(data_axes) | {
            a for a, size in mesh.shape.items()
            if size == 1 and not (a == "model" and settings.moe_impl == "ep")}

        def stepped(values, opt_state, batch, step, *extra):
            vspecs = self.value_specs(env, values)
            ospecs = self.opt_specs(env, opt_state, vspecs)
            in_specs = (vspecs, ospecs, batch_specs(batch, data_axes), P(),
                        *[replicated_specs(b) for b in extra])
            out_specs = (vspecs, ospecs, P()) + (
                (replicated_specs(values),) if ret_agg else ())
            fn = jax.shard_map(worker_fn, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs, axis_names=manual,
                               check_vma=False)
            return fn(values, opt_state, batch, step, *extra)

        if self.needs_basis:
            def step_fn(values, opt_state, batch, step, basis):
                return stepped(values, opt_state, batch, step, *basis)
        else:
            step_fn = stepped

        return StepBundle(self.name, step_fn, ctx, settings,
                          needs_basis=self.needs_basis,
                          returns_aggregate=ret_agg,
                          value_shardings=env.get("value_shardings"),
                          plan=env.get("plan"))


@TRAIN_STRATEGIES.register("replicated")
class ReplicatedStrategy(_StrategyBase):
    """Params replicated over the worker axes; AGG_FNS aggregation.

    Exactly the paper's setup: each data shard is one Byzantine-fault-
    containment unit, aggregation is CGC (or any ``AGG_FNS`` entry) over
    the worker axes, every worker applies the identical update.
    """

    name = "replicated"

    def validate(self, settings, ctx, mesh):
        if settings.aggregator not in AGG_FNS:
            raise ValueError(f"unknown aggregator {settings.aggregator!r}; "
                             f"known: {sorted(AGG_FNS)}")

    def prepare(self, cfg, opt, settings, mesh, ctx):
        if settings.moe_impl == "ep" and mesh is not None:
            # expert parallelism runs a NESTED shard_map over the model
            # axis (disjoint from the worker's manual data axes): batch
            # is already local, so batch_axes=() inside.
            inner = ShardCtx(mesh=mesh, batch_axes=(), model_axis="model",
                             moe_impl="ep", remat=settings.remat)
        else:
            inner = (ShardCtx(remat=settings.remat)
                     if settings.remat != "full" else None)
        return {"inner_ctx": inner}

    def make_loss_fn(self, cfg, settings, mesh, ctx, env):
        inner = env["inner_ctx"]
        # inside the worker shard_map the batch is already local -> the
        # MoE layer dispatches locally (model axis auto) unless EP.
        return lambda values, batch: M.train_loss(values, cfg, batch,
                                                  shard_ctx=inner)

    def aggregate(self, env, grads, settings, data_axes, extra):
        if not data_axes:
            return grads, {}
        return AGG_FNS[settings.aggregator](grads, data_axes, settings.f)


@TRAIN_STRATEGIES.register("fsdp")
class FsdpStrategy(_StrategyBase):
    """FSDP (§Perf HC1): params + opt state sharded over the data axes,
    per-layer just-in-time gathers, blockwise CGC on the reduce-scatter
    (dist/fsdp.py). ``value_shardings`` on the bundle carries the
    NamedShardings the driver must place operands with (params are
    LOGICALLY global; FSDP is purely a placement + spec concern).
    """

    name = "fsdp"

    def validate(self, settings, ctx, mesh):
        if settings.aggregator not in ("cgc", "mean"):
            raise ValueError(
                f"FSDP trainer supports aggregator 'cgc' or 'mean' (the "
                f"reduction happens inside the gather VJP), got "
                f"{settings.aggregator!r}")
        if not ctx.batch_axes:
            raise ValueError("FSDP needs a data-parallel axis")
        if settings.n_byz:
            raise ValueError("Byzantine injection is incompatible with FSDP "
                             "(per-worker grads never materialise whole); "
                             "use the replicated trainer to exercise attacks")
        if settings.return_aggregate:
            raise ValueError("return_aggregate is incompatible with FSDP: "
                             "planned gradient leaves are shard-local after "
                             "the reduce-scatter, so no replicated aggregate "
                             "pytree exists to emit")

    def prepare(self, cfg, opt, settings, mesh, ctx):
        from repro.dist.fsdp import (fsdp_manual_specs, fsdp_tree_shardings,
                                     make_gather_fn, plan_fsdp)
        from repro.launch.specs import abstract_params

        data_axes = ctx.batch_axes
        params_abs = abstract_params(cfg)
        plan = plan_fsdp(params_abs, mesh, dp_axes=data_axes)
        # layers subtree gathers inside the scan; everything else up-front.
        plan_top = dict(plan)
        layer_plan = plan_top.pop("layers", None)
        top_plan_full = dict(plan_top)
        if layer_plan is not None:
            top_plan_full["layers"] = jax.tree.map(
                lambda _: None, layer_plan, is_leaf=lambda x: x is None)
        use_cgc = settings.aggregator == "cgc"
        gather_top = make_gather_fn(top_plan_full, data_axes, settings.f,
                                    use_cgc)
        layer_gf = (make_gather_fn(layer_plan, data_axes, settings.f,
                                   use_cgc, strip_layer_dim=True)
                    if layer_plan is not None else None)
        inner_ctx = dataclasses.replace(ShardCtx(), layer_gather=layer_gf,
                                        remat=settings.remat)
        return {
            "plan": plan,
            "use_cgc": use_cgc,
            "gather_top": gather_top,
            "inner_ctx": inner_ctx,
            "vspecs": fsdp_manual_specs(params_abs, plan, data_axes),
            "value_shardings": fsdp_tree_shardings(params_abs, mesh, plan,
                                                   dp_axes=data_axes),
        }

    def make_loss_fn(self, cfg, settings, mesh, ctx, env):
        gather_top, inner = env["gather_top"], env["inner_ctx"]
        return lambda values, batch: M.train_loss(gather_top(values), cfg,
                                                  batch, shard_ctx=inner)

    def aggregate(self, env, grads, settings, data_axes, extra):
        # fsdp leaves: already blockwise-clipped + reduce-scattered in the
        # gather VJP; the replicated remainder gets the exact matching psum.
        from repro.dist.fsdp import aggregate_rest_cgc
        return aggregate_rest_cgc(grads, env["plan"], data_axes, settings.f,
                                  use_cgc=env["use_cgc"]), {}

    def clip(self, env, grads, settings, data_axes):
        # layout-aware: planned leaves are shards, rest is replicated
        from repro.dist.fsdp import clip_fsdp_global_norm
        return clip_fsdp_global_norm(grads, env["plan"], data_axes,
                                     settings.clip_norm)

    def value_specs(self, env, values):
        return env["vspecs"]

    def opt_specs(self, env, opt_state, vspecs):
        return mirror_opt_specs(vspecs, opt_state)


@TRAIN_STRATEGIES.register("echo_dp")
class EchoDpStrategy(_StrategyBase):
    """Echo-compressed DP step (dist/echo_dp.py — §Perf HC3).

    ``step(values, opt_state, batch, step, basis) -> (values, opt_state,
    metrics, aggregate)`` where ``basis`` is a list of echo_k reference
    pytrees (recent raw-round aggregates, replicated on every worker).
    ``metrics["all_echo"]`` reports whether the fast path was valid —
    the :class:`Trainer` re-runs the round with the exact CGC step when
    it is not, and rolls ``basis`` with that raw aggregate.

    The trailing ``basis`` list doubles as the control-plane data path:
    after the echo_k reference pytrees, ``settings.dynamic_r`` appends a
    traced Eq. 7 threshold scalar and ``settings.ef`` appends the
    replicated (n, K) error-feedback residual state — both ride the same
    replicated extras plumbing, so a policy retuning r (or the residual
    carrying across rounds) never triggers a recompile.
    """

    name = "echo_dp"
    needs_basis = True

    def validate(self, settings, ctx, mesh):
        if not ctx.batch_axes:
            raise ValueError("echo-DP aggregation needs a data-parallel axis")

    def make_loss_fn(self, cfg, settings, mesh, ctx, env):
        return lambda values, batch: M.train_loss(values, cfg, batch,
                                                  shard_ctx=None)

    def aggregate(self, env, grads, settings, data_axes, extra):
        extra = list(extra)
        basis, rest = extra[:settings.echo_k], extra[settings.echo_k:]
        r = rest.pop(0) if settings.dynamic_r else settings.echo_r
        ef = rest.pop(0) if settings.ef else None
        gram = basis_gram(basis)
        # lossy codecs quantize the transmitted coefficient vectors; the
        # lossless default keeps the jaxpr identical to the pre-comm step.
        codec = settings.comm.codec if settings.comm is not None else None
        if codec is not None and codec.lossless:
            codec = None
        agg, all_echo, diags = echo_dp_aggregate(
            grads, basis, gram, data_axes, settings.f, r,
            codec=codec, ef=ef)
        return agg, dict(diags, all_echo=all_echo)


# The shared plugin registry (repro.run.registry): a new strategy is one
# @TRAIN_STRATEGIES.register("name") class implementing TrainStrategy.
STRATEGIES = TRAIN_STRATEGIES


# ---------------------------------------------------------------------------
# Shardings for the step operands (shared sharding helpers)
# ---------------------------------------------------------------------------


def param_shardings(params_tree, mesh, rules=None):
    return tree_shardings(params_tree, mesh, rules)


def batch_shardings(batch_specs_tree, mesh, rules=None):
    return tree_shardings(batch_specs_tree, mesh, rules)


def opt_state_shardings(opt_state_abs, params_tree, mesh, rules=None,
                        override=None):
    """Mirror parameter shardings onto the optimizer state by path suffix.

    ``override``: a plain sharding tree (e.g. FSDP shardings) to mirror
    instead of the default rule-derived one. The lookup is a dict keyed
    by the param paths, probed with progressively shorter "/"-suffixes
    of each opt-state path — O(depth) per leaf instead of the old
    O(params) scan, and longest-suffix-first instead of insertion order.
    """
    from repro.checkpoint.ckpt import _flatten_with_paths, _path_str
    pshard = override if override is not None else tree_shardings(
        params_tree, mesh, rules)
    by_path = _flatten_with_paths(pshard)
    rep = NamedSharding(mesh, P())

    leaves = []
    for path, _ in jax.tree_util.tree_flatten_with_path(opt_state_abs)[0]:
        parts = [_path_str(p) for p in path]
        sh = rep
        for i in range(len(parts)):
            cand = "/".join(parts[i:])
            if cand in by_path:
                sh = by_path[cand]
                break
        leaves.append(sh)
    treedef = jax.tree_util.tree_structure(opt_state_abs)
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Metrics sink
# ---------------------------------------------------------------------------


def _train_record_line(record: Dict[str, Any]) -> str:
    parts = [f"step {record.get('step', 0):5d}",
             f"loss={record.get('loss', 0.0):.4f}"]
    if "all_echo" in record:
        parts.append(f"all_echo={record['all_echo']}")
    if "bits_cumulative" in record:
        parts.append(f"bits={record['bits_cumulative']:.3e}")
    return "  ".join(parts)


class MetricsSink:
    """Per-round metrics writer: jsonl file (every round) + stdout
    (every ``log_every`` rounds). ``printer`` is pluggable for tests;
    ``formatter`` maps a record to its stdout line (default: the trainer
    step/loss/bits line — ``repro.serve`` passes its own).

    jsonl writes are non-blocking: ``emit`` enqueues the serialised
    record and returns; the shared :class:`repro.obs.AsyncLineWriter`
    drains the queue to the file so metrics I/O stays off the driver
    hot loop. ``flush`` blocks until everything enqueued so far is on
    disk; ``close`` flushes, stops the thread and closes the file. Both
    re-raise the first background write error (the
    ``AsyncCheckpointWriter`` contract), and the writer's atexit hook
    lands the tail records even when a run crashes past ``close``.
    """

    def __init__(self, path: Optional[str] = None, log_every: int = 5,
                 printer: Optional[Callable[[str], None]] = None,
                 formatter: Optional[Callable[[Dict[str, Any]], str]] = None):
        self.log_every = max(int(log_every), 1)
        self._writer = AsyncLineWriter(path) if path else None
        self._print = (lambda s: print(s, flush=True)) \
            if printer is None else printer
        self._format = formatter or _train_record_line

    def emit(self, record: Dict[str, Any]) -> None:
        if self._writer is not None:
            self._writer.write(json.dumps(record) + "\n")
        step = record.get("step", 0)
        if step % self.log_every == 0 or record.get("final"):
            self._print(self._format(record))

    def flush(self, timeout: Optional[float] = None) -> bool:
        """Block until every record emitted so far is written to disk.
        Default blocks indefinitely (the durability the old synchronous
        sink had); with a timeout, returns False if it expired. Raises
        if the background writer hit an error."""
        if self._writer is None:
            return True
        return self._writer.flush(timeout)

    def close(self) -> None:
        if self._writer is not None:
            writer, self._writer = self._writer, None
            writer.close()


# ---------------------------------------------------------------------------
# Trainer: the driver loop
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    log_every: int = 5
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 0             # 0: checkpoint only at the end of fit()
    resume: bool = False
    metrics_path: Optional[str] = None  # jsonl sink
    # When the echo basis rolls: "raw" (only after raw/fallback rounds —
    # the paper's reference set R holds overheard RAW gradients; echo
    # aggregates lie in span(basis) and add no information) or "always".
    roll_policy: str = "raw"
    # jax.profiler trace window over ``profile_steps`` rounds of fit()
    # from its second round on, after the first round has compiled (0 =
    # off), written to ``profile_dir``. A profiler failure fails the run.
    profile_steps: int = 0
    profile_dir: Optional[str] = None


@dataclasses.dataclass
class TrainState:
    """Everything a resume needs: (values, opt_state, step, basis) plus
    the (n, K) error-feedback residuals when ``TrainSettings.ef`` is on."""

    values: Any
    opt_state: Any
    step: int = 0
    basis: Optional[List[Any]] = None
    ef: Optional[jax.Array] = None


class Trainer:
    """Owns the real training loop over a built :class:`StepBundle`.

    For :class:`EchoDpStrategy` each round first runs the optimistic
    coefficient-space step; when any worker fails the echo test (Eq. 7)
    the round is re-run with the exact CGC step (``ReplicatedStrategy``
    with ``return_aggregate=True``) and the basis rolls with the raw
    aggregate.

    Communication accounting flows through ``repro.comm``: the wire
    codec prices every round (an echo attempt costs
    ``n * echo_msg_bits(n, K)``, a raw/fallback round adds
    ``n * raw_msg_bits(d)``, the all-raw baseline is ``n *
    raw_msg_bits(d)`` per round — the paper's closed form under fp32),
    the broadcast channel can fade echo slots (forcing the raw fallback,
    seeded + reproducible) or refuse over-budget attempts, and every
    round reports into one :class:`~repro.comm.CommLedger` whose fields
    feed the metrics sink. Checkpoint writes happen on a background
    thread (``ckpt_lib.AsyncCheckpointWriter``) so the driver loop never
    blocks on .npz serialization; ``restore``/``close`` flush it.
    """

    def __init__(self, strategy, cfg, opt: Optimizer,
                 settings: TrainSettings, mesh, global_batch: int,
                 config: TrainerConfig = TrainerConfig(),
                 loss_fn: Optional[Callable] = None,
                 printer: Optional[Callable[[str], None]] = None,
                 hooks: Optional[obs.Hooks] = None):
        if isinstance(strategy, str):
            strategy = STRATEGIES[strategy](loss_fn=loss_fn)
        self.strategy = strategy
        self.opt = opt
        self.settings = settings
        self.config = config
        self.mesh = mesh
        self._model_cfg = cfg
        self._global_batch = global_batch
        self.comm = settings.comm if settings.comm is not None \
            else DEFAULT_COMM
        self.bundle = strategy.build(cfg, opt, settings, mesh, global_batch)
        # Donation audit (DESIGN.md §10): a plain round consumes
        # (values, opt_state) and returns their successors, so both
        # buffers are donated — XLA updates weights and moments in place
        # instead of holding two copies of the model live. The echo-DP
        # OPTIMISTIC step must NOT donate: when Eq. 7 fails its outputs
        # are discarded and the same inputs re-enter the exact fallback
        # step, so they have to survive the call. The fallback itself is
        # terminal for the round and donates. Batches are never donated
        # (callers may replay them).
        donate = () if self.bundle.needs_basis else (0, 1)
        self.step_fn = jax.jit(self.bundle.fn, donate_argnums=donate)
        self.fallback_fn = None
        if self.bundle.needs_basis:
            fb = ReplicatedStrategy(
                loss_fn=getattr(strategy, "loss_override", None))
            fb_settings = dataclasses.replace(settings,
                                              return_aggregate=True)
            self.fallback_bundle = fb.build(cfg, opt, fb_settings, mesh,
                                            global_batch)
            self.fallback_fn = jax.jit(self.fallback_bundle.fn,
                                       donate_argnums=(0, 1))
        self.sink = MetricsSink(config.metrics_path, config.log_every,
                                printer)
        self.hooks = obs.as_hooks(hooks)
        self.n_workers = self.bundle.ctx.num_workers
        self._d: Optional[int] = None
        self.ledger = CommLedger()
        self._ckpt_writer: Optional[ckpt_lib.AsyncCheckpointWriter] = None
        self._first_loss: Optional[float] = None
        self._last_loss: Optional[float] = None
        # Control plane (repro.comm.policy): a dynamic policy retunes
        # (codec, echo_r, budget) per round from the previous round's
        # observation; a static one only emits its constant decisions.
        self.policy = settings.policy
        self._policy_dynamic = (self.policy is not None
                                and not getattr(self.policy, "static",
                                                False))
        self._policy_ready = False
        self._last_obs = None
        self._cur_codec_name = self.comm.codec.name
        self._cur_r = float(settings.echo_r)
        self._cur_budget: Optional[int] = None
        self.codec_switches = 0
        self._fp32_cum = 0
        self._codec_cache: Dict[str, Any] = {self.comm.codec.name:
                                             self.comm.codec}
        self._opt_steps: Dict[str, Callable] = {}

    # Legacy counter surface — reads delegate to the comm ledger, which
    # is the single accounting authority now.

    @property
    def n_rounds(self) -> int:
        return self.ledger.rounds

    @property
    def n_echo(self) -> int:
        return self.ledger.echo_rounds

    @property
    def bits_sent(self) -> int:
        return self.ledger.bits_sent

    @property
    def bits_baseline(self) -> int:
        return self.ledger.bits_baseline

    # --- state management -------------------------------------------

    def init_state(self, values, opt_state=None) -> TrainState:
        """Fresh state (placed per the strategy's shardings); resumes
        from ``config.ckpt_dir`` when ``config.resume`` is set and a
        checkpoint exists."""
        # the step fns donate their (values, opt_state) arguments, so the
        # state must own its buffers — never alias what the caller holds
        values = jax.tree.map(jnp.copy, values)
        if self.bundle.value_shardings is not None:
            values = jax.device_put(values, self.bundle.value_shardings)
        if opt_state is None:
            opt_state = self.opt.init(values)
        else:
            opt_state = jax.tree.map(jnp.copy, opt_state)
        basis = (init_basis(values, self.settings.echo_k)
                 if self.bundle.needs_basis else None)
        ef = None
        if self.bundle.needs_basis and self.settings.ef:
            from repro.comm.policy import ef_init
            ef = ef_init(self.n_workers, self.settings.echo_k)
        state = TrainState(values, opt_state, 0, basis, ef)
        cfg = self.config
        if cfg.resume and cfg.ckpt_dir \
                and ckpt_lib.latest_step(cfg.ckpt_dir) is not None:
            state = self.restore(state)
        return state

    def restore(self, like: TrainState, step: Optional[int] = None
                ) -> TrainState:
        if self._ckpt_writer is not None:
            self._ckpt_writer.flush()     # pending async saves land first
        extra_like = {"basis": like.basis} if like.basis is not None else None
        if extra_like is not None and like.ef is not None:
            extra_like["ef"] = like.ef
        values, opt_state, extra, at, complete = ckpt_lib.restore_train_state(
            self.config.ckpt_dir, like.values, like.opt_state,
            extra_like=extra_like, step=step)
        if self.bundle.value_shardings is not None:
            values = jax.device_put(values, self.bundle.value_shardings)
            oshard = opt_state_shardings(
                opt_state, None, self.mesh,
                override=self.bundle.value_shardings)
            opt_state = jax.device_put(opt_state, oshard)
        if not complete:
            # pre-v1 checkpoint: values only — keep the fresh opt/basis.
            opt_state = self.opt.init(values)
        basis = (extra or {}).get("basis", like.basis) \
            if extra is not None else like.basis
        ef = (extra or {}).get("ef", like.ef) \
            if extra is not None else like.ef
        return TrainState(values, opt_state, at, basis, ef)

    def save(self, state: TrainState, wait: bool = True) -> Optional[str]:
        """Checkpoint ``state``; returns the target .npz path.

        The write runs on the background checkpoint thread.
        ``wait=True`` (the default for direct calls) blocks until it is
        on disk; the driver loop passes ``wait=False`` so periodic
        checkpoints never stall training. An async save snapshots the
        state to host memory first: the step fns donate their input
        buffers, so by the time the writer thread serializes, the
        device arrays of this round may already have been consumed by
        the next one.
        """
        if not self.config.ckpt_dir:
            return None
        if self._ckpt_writer is None:
            self._ckpt_writer = ckpt_lib.AsyncCheckpointWriter()
        values, opt_state = state.values, state.opt_state
        extra_state = ({"basis": state.basis}
                       if state.basis is not None else None)
        if extra_state is not None and state.ef is not None:
            extra_state["ef"] = state.ef
        if not wait:
            snap = lambda t: jax.tree.map(      # noqa: E731
                lambda x: np.array(x, copy=True), t)
            values, opt_state = snap(values), snap(opt_state)
            extra_state = snap(extra_state)
        path = self._ckpt_writer.submit(
            self.config.ckpt_dir, state.step, values, opt_state,
            extra_state=extra_state,
            extra={"strategy": self.bundle.name})
        if wait:
            self._ckpt_writer.flush()
        return path

    # --- the loop ----------------------------------------------------

    def _grad_dim(self, values) -> int:
        if self._d is None:
            self._d = int(sum(v.size for v in jax.tree.leaves(values)))
        return self._d

    # --- the control plane (repro.comm.policy, DESIGN.md §13) --------

    def _codec_obj(self, name: str):
        """Codec instance for a policy-decided name (the configured
        instance when the name matches — keeping e.g. a custom topk k —
        registry defaults otherwise)."""
        codec = self._codec_cache.get(name)
        if codec is None:
            from repro.run.registry import CODECS
            codec = self._codec_cache[name] = CODECS[name](None)
        return codec

    def _ensure_policy(self, d: int) -> None:
        """One-time policy setup: topology, starting point, price list."""
        if self._policy_ready:
            return
        from repro.comm.policy import CODEC_LADDER, PolicyContext
        n, K = self.n_workers, self.settings.echo_k
        raw = {c: int(raw_round_bits(self._codec_obj(c), n, d))
               for c in CODEC_LADDER}
        echo = {c: n * int(self._codec_obj(c).echo_msg_bits(n, K))
                for c in CODEC_LADDER} if self.bundle.needs_basis \
            else {c: 0 for c in CODEC_LADDER}
        chan = self.comm.channel
        self.policy.setup(PolicyContext(
            n=n, d=d, echo_k=K, codec=self.comm.codec.name,
            echo_r=float(self.settings.echo_r), channel=chan.name,
            drop_prob=float(getattr(chan, "drop_prob", 0.0)),
            budget_bits=int(getattr(chan, "budget_bits", 0)),
            raw_round_bits=raw, echo_round_bits=echo))
        self._policy_ready = True

    def _opt_step_for(self, codec_name: str) -> Callable:
        """The jitted optimistic step for one policy-decided codec.

        Built lazily and cached per codec name (the ladder bounds the
        cache at 4 entries); each bundle carries ``dynamic_r=True`` so
        Eq. 7's r arrives as a traced scalar — the policy can retune it
        every round without a single recompile. Optimistic steps never
        donate (their outputs are discarded on fallback).
        """
        fn = self._opt_steps.get(codec_name)
        if fn is None:
            s = dataclasses.replace(
                self.settings, dynamic_r=True,
                comm=CommConfig(channel=self.comm.channel,
                                codec=self._codec_obj(codec_name)))
            bundle = type(self.strategy)(
                loss_fn=getattr(self.strategy, "loss_override", None)
            ).build(self._model_cfg, self.opt, s, self.mesh,
                    self._global_batch)
            fn = self._opt_steps[codec_name] = jax.jit(bundle.fn,
                                                       donate_argnums=())
        return fn

    def _policy_decide(self, step: int, d: int):
        """Ask the policy for this round's (codec, channel, echo_r).

        Without a policy this is a passthrough of the configured comm.
        With one, the previous round's observation feeds ``observe`` and
        the decision is applied — but only a *dynamic* policy actually
        changes anything; a static policy's constant decision is emitted
        as events/counters and otherwise ignored, keeping the trajectory
        bitwise identical to the no-policy engine.
        """
        codec, channel = self.comm.codec, self.comm.channel
        echo_r = float(self.settings.echo_r)
        if self.policy is None:
            return codec, channel, echo_r
        self._ensure_policy(d)
        decision = self.policy.observe(self._last_obs)
        obs.counter("comm.policy.decisions")
        switched = r_changed = False
        if self._policy_dynamic:
            if decision.codec is not None \
                    and decision.codec != self._cur_codec_name:
                self._cur_codec_name = decision.codec
                self.codec_switches += 1
                switched = True
                obs.counter("comm.policy.codec_switches")
            if decision.echo_r is not None \
                    and float(decision.echo_r) != self._cur_r:
                self._cur_r = float(decision.echo_r)
                r_changed = True
                obs.counter("comm.policy.echo_r_changes")
            if decision.budget_bits is not None:
                self._cur_budget = int(decision.budget_bits)
            codec = self._codec_obj(self._cur_codec_name)
            echo_r = self._cur_r
            if self._cur_budget is not None \
                    and hasattr(channel, "budget_bits"):
                channel = dataclasses.replace(channel,
                                              budget_bits=self._cur_budget)
        if switched or r_changed:
            obs.event("comm.policy.decision", step=step,
                      policy=self.policy.name, codec=codec.name,
                      echo_r=echo_r, codec_switched=switched,
                      echo_r_changed=r_changed)
        return codec, channel, echo_r

    def _step_and_extras(self, state: TrainState, codec, echo_r: float):
        """The optimistic step fn + its trailing extras list: the basis,
        then (dynamic policies) the traced Eq. 7 threshold, then (ef)
        the residual state — matching ``EchoDpStrategy.aggregate``."""
        extras = list(state.basis)
        if self._policy_dynamic:
            fn = self._opt_step_for(codec.name)
            extras.append(jnp.asarray(echo_r, F32))
        else:
            fn = self.step_fn
        if self.settings.ef and state.ef is not None:
            extras.append(state.ef)
        return fn, extras

    def _observe_round(self, state: TrainState, codec, echo_r: float,
                       bits: int, raw_round: int, loss: float,
                       echoed: bool, attempted: bool, drops: int,
                       led: Dict[str, Any]) -> None:
        """Record the finished round for the policy + the obs stream."""
        from repro.comm import FP32
        from repro.comm.policy import RoundObservation
        n, d = self.n_workers, self._d
        fp32_round = raw_round_bits(FP32, n, d)
        self._fp32_cum += fp32_round
        self._last_obs = RoundObservation(
            round=state.step, bits=bits, baseline_bits=raw_round,
            fp32_baseline_bits=fp32_round, loss=loss, codec=codec.name,
            echo_r=echo_r, attempted=attempted, echoed=echoed,
            echo_drops=drops, refused=self.bundle.needs_basis
            and not attempted)
        obs.event("comm.policy.round", step=state.step,
                  policy=self.policy.name, codec=codec.name,
                  echo_r=echo_r, bits=bits, echoed=echoed,
                  attempted=attempted, echo_drops=drops,
                  bits_cumulative=led["bits_cumulative"],
                  fp32_baseline_cumulative=self._fp32_cum, loss=loss)

    def run_round(self, state: TrainState, batch
                  ) -> Tuple[TrainState, Dict[str, Any]]:
        """One driver round; returns (new_state, metrics record).

        The round is a ``train.round`` span whose children are the
        dispatch of the optimistic / fallback / plain step (each ends
        when the dispatch returns), every host wait on the device
        (``sync``) and the bookkeeping after it (``record``); each span is
        a profiler annotation too. It fires ``hooks.on_round_start/end``
        around the round — host-side only, so the jitted computation (and
        the trajectory) is untouched.
        """
        self.hooks.on_round_start(state.step)
        with obs.span("train.round"):
            new_state, record = self._round_body(state, batch)
        self.hooks.on_round_end(record["step"], record)
        return new_state, record

    def _round_body(self, state: TrainState, batch
                    ) -> Tuple[TrainState, Dict[str, Any]]:
        step_arr = jnp.asarray(state.step)
        n = self.n_workers
        d = self._grad_dim(state.values)
        codec, channel, echo_r = self._policy_decide(state.step, d)
        # A routed channel (repro.net.relay) multiplies every message by
        # its copy count; scaling the baseline too keeps the echo-vs-raw
        # saving a property of the protocol, not the medium.
        price = channel.price_factor()
        raw_round = raw_round_bits(codec, n, d) * price
        record: Dict[str, Any] = {"step": state.step,
                                  "strategy": self.bundle.name}
        echoed = False
        attempted, drops = False, 0
        new_ef = state.ef

        if self.bundle.needs_basis:
            K = self.settings.echo_k
            echo_round = n * int(codec.echo_msg_bits(n, K))
            # A metered channel can refuse the optimistic attempt when a
            # whole echo round would blow the per-round budget.
            attempted = channel.allows_bits(echo_round)
            # A faded echo slot cannot be verified: its sender retransmits
            # raw, so the coefficient-space aggregate (which needs every
            # echo delivered) is invalid and the round falls back. The
            # draw depends only on (seed, step, n) — the bits trajectory
            # replays exactly — so it happens BEFORE the optimistic step:
            # a round the channel already doomed skips straight to the
            # fallback instead of paying for two full train steps.
            drops = channel.round_echo_drops(state.step, n) if attempted \
                else 0
            all_echo = False
            if attempted and drops == 0:
                opt_fn, extras = self._step_and_extras(state, codec, echo_r)
                with obs.span("optimistic"):
                    v, o, m, agg = opt_fn(state.values,
                                          state.opt_state,
                                          batch, step_arr,
                                          extras)
                with obs.span("sync"):
                    all_echo = bool(m["all_echo"])
            echoed = attempted and all_echo and drops == 0
            if echoed:
                rolled = self.config.roll_policy == "always"
                basis = roll_basis(state.basis, agg) if rolled \
                    else state.basis
                # error-feedback residuals commit only on rounds whose
                # transmission was used; a discarded attempt keeps state
                if self.settings.ef and "ef_state" in m:
                    new_ef = m["ef_state"]
            else:
                # optimistic round invalid (Eq. 7 failed, echo slots
                # faded, or never attempted): fall back to the exact CGC
                # step and roll the basis with the raw aggregate. The
                # discarded attempt's weights and aggregate are released
                # first: at published widths they would not fit beside it.
                v = o = agg = None
                with obs.span("fallback"):
                    v, o, m, agg = self.fallback_fn(
                        state.values, state.opt_state, batch, step_arr)
                    basis = roll_basis(state.basis, agg)
                rolled = True
            bits = round_comm_bits(codec, n, d, K, all_echo and drops == 0,
                                   attempted) * price
            record.update(all_echo=echoed, basis_rolled=rolled)
            if drops:
                record["echo_drops"] = drops
            if not attempted:
                record["comm_refused"] = True
            new_state = TrainState(v, o, state.step + 1, basis, new_ef)
        else:
            with obs.span("step"):
                out = self.step_fn(state.values, state.opt_state, batch,
                                   step_arr)
                v, o, m = out[0], out[1], out[2]
            bits = raw_round
            new_state = TrainState(v, o, state.step + 1, None)

        # each host wait on the device is a span of its own: the
        # dispatch spans above end when the dispatch returns
        with obs.span("sync"):
            loss = float(m["loss"])
        with obs.span("record"):
            if self._first_loss is None:
                self._first_loss = loss
            self._last_loss = loss
            led = self.ledger.record_round(bits=bits, baseline=raw_round,
                                           echoed=echoed)
            record.update(loss=loss, **led)
            for k in ("echo_frac", "grad_global_norm", "cgc_threshold",
                      "cgc_clipped_frac", "ef_residual_norm",
                      "moe_held_copies", "moe_load_ratio"):
                if k in m:
                    record[k] = float(m[k])
            if "moe_held_copies" in m:
                record["moe_dropped"] = float(m["moe_dropped"])
            if self._policy_dynamic:
                record["codec"] = codec.name
                record["echo_r"] = echo_r
            if self.policy is not None:
                self._observe_round(state, codec, echo_r, bits, raw_round,
                                    loss, echoed, attempted, drops, led)
            self.sink.emit(record)
        return new_state, record

    def _profile_rounds(self, first: int, steps: int):
        """The rounds ``[start, stop)`` of a fit() from round ``first`` to
        ``steps`` that the profiler traces: ``profile_steps`` of them from
        the fit's second round on, so that the first round's compilation
        stays out of the trace. None when no profile is asked for. A
        profile that was asked for and cannot be taken fails the run: a
        run that silently carries on unprofiled would look like one that
        was measured."""
        cfg = self.config
        if not cfg.profile_steps or not cfg.profile_dir:
            return None
        if steps - first < 2:
            raise ValueError(
                f"profile_steps={cfg.profile_steps} needs a fit of at least "
                f"2 rounds (the first compiles and is not profiled); this "
                f"one runs {max(steps - first, 0)}")
        return first + 1, first + 1 + cfg.profile_steps

    def _profiler_start(self) -> None:
        jax.profiler.start_trace(self.config.profile_dir)
        obs.event("train.profile_start", dir=self.config.profile_dir,
                  steps=self.config.profile_steps)

    def _profiler_stop(self) -> None:
        jax.profiler.stop_trace()
        obs.event("train.profile_stop", dir=self.config.profile_dir)

    def fit(self, state: TrainState, batches: Iterator, steps: int
            ) -> Tuple[TrainState, Dict[str, Any]]:
        """Run rounds until ``state.step`` reaches ``steps`` (absolute —
        a resumed state continues from its checkpointed step)."""
        cfg = self.config
        t0 = time.time()
        window = self._profile_rounds(state.step, steps)
        profiling = False
        try:
            while state.step < steps:
                if window and state.step == window[0]:
                    self._profiler_start()
                    profiling = True
                with obs.span("train.data"):
                    batch = next(batches)
                state, _ = self.run_round(state, batch)
                if profiling and state.step >= window[1]:
                    self._profiler_stop()
                    profiling = False
                if cfg.ckpt_dir and cfg.ckpt_every \
                        and state.step % cfg.ckpt_every == 0 \
                        and state.step < steps:
                    with obs.span("train.checkpoint"):
                        self.save(state, wait=False)  # off the driver
        finally:
            if profiling:        # fit ended inside the window (or crashed)
                self._profiler_stop()
        if cfg.ckpt_dir:
            # the final snapshot is synchronous: fit() returning means it
            # is durable even if the caller never close()s (the periodic
            # saves above are the ones that must stay off the hot loop).
            with obs.span("train.checkpoint"):
                self.save(state)
        summary = self.summary()
        summary["wall_s"] = round(time.time() - t0, 2)
        return state, summary

    def close(self) -> None:
        """Release the metrics sink and the background checkpoint writer
        (call when done with the Trainer — fit() can be called again to
        continue, so it never closes)."""
        self.sink.close()
        if self._ckpt_writer is not None:
            self._ckpt_writer.close()
            self._ckpt_writer = None

    def summary(self) -> Dict[str, Any]:
        led = self.ledger.summary()
        s: Dict[str, Any] = {
            "strategy": self.bundle.name,
            "rounds": led["rounds"],
            "workers": self.n_workers,
            "bits_sent": led["bits_sent"],
            "bits_baseline": led["bits_baseline"],
            "first_loss": self._first_loss,
            "final_loss": self._last_loss,
        }
        if self.bundle.needs_basis and led["rounds"]:
            s["echo_rounds"] = led["echo_rounds"]
            s["echo_rate"] = led["echo_rate"]
            s["bits_saving"] = led["bits_saving"]
        if self.policy is not None:
            s["policy"] = self.policy.name
            s["codec_switches"] = self.codec_switches
            if self._policy_dynamic:
                s["codec_final"] = self._cur_codec_name
                s["echo_r_final"] = self._cur_r
        return s
