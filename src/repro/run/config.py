"""The declarative job config tree (DESIGN.md §8).

One frozen-dataclass tree describes a whole experiment — the paper's
sweep axes (n, f, attack, aggregator, cost function) plus the systems
knobs (strategy, mesh, serving shapes) — and round-trips losslessly
through JSON, so every run can emit its exact configuration next to its
metrics:

    RunConfig
      model     ModelSpec | None   architecture (None: quadratic cost runs)
      mesh      MeshSpec           host-device forcing + MoE impl
      scenario  ScenarioSpec       aggregator / attack / f / echo / data / comm
      train     TrainSpec | None   trainer workload
      serve     ServeSpec | None   serving workload (incl. sampling)
      dryrun    DryrunSpec | None  lower+compile workload
      bench     BenchSpec | None   serve benchmark workload
      obs       ObsSpec            tracker sink + events path

``to_json``/``from_json`` carry a ``schema_version`` field; unknown keys
are rejected with the known alternatives listed. ``apply_overrides``
implements the CLI's dotted-path ``--set train.steps=3`` edits with
field-type coercion. This module imports neither jax nor any repro
sibling, so config parsing stays instant.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import typing
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

SCHEMA_VERSION = 1


# ---------------------------------------------------------------------------
# Leaf specs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SamplingSpec:
    """Token sampling policy for serving.

    ``temperature == 0`` is exact greedy argmax (the default — bitwise
    the pre-sampling engine). ``temperature > 0`` softmax-samples, with
    the distribution truncated to the ``top_k`` largest logits when
    ``top_k > 0`` and/or to the nucleus (smallest set of tokens whose
    cumulative probability reaches ``top_p``) when ``0 < top_p < 1`` —
    both filters compose, top-k first. ``seed`` makes runs reproducible:
    the engine derives one PRNG key per dispatch from it, so the same
    submissions produce the same tokens.
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0               # 0 (or >= 1) disables nucleus
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class CommSpec:
    """Communication setup: how gradients/echoes are wire-coded and what
    broadcast medium carries them (``repro.comm``, DESIGN.md §9).

    ``codec`` prices (and for lossy codecs, quantizes) every message;
    ``channel`` is the single-hop radio model. ``policy`` selects the
    closed-loop controller that may retune codec / echo_r / budget per
    round from ledger measurements (``repro.comm.policy``, DESIGN.md
    §13); ``ef`` turns on per-worker error-feedback accumulators so
    lossy codecs stay convergent. The defaults are the paper's ideal
    reliable fp32 broadcast with the static policy — bitwise the
    pre-comm stack.
    """

    channel: str = "ideal"           # registry: channels (ideal|lossy|metered)
    codec: str = "fp32"              # registry: codecs (fp32|bf16|int8|topk)
    drop_prob: float = 0.0           # lossy: per-slot fade probability
    seed: int = 0                    # channel PRNG seed
    budget_bits: int = 0             # metered: per-round bit budget (0 = off)
    topk: int = 32                   # topk codec: entries kept per vector
    policy: str = "static"           # registry: comm_policies
    ef: bool = False                 # error-feedback residual accumulators


@dataclasses.dataclass(frozen=True)
class NetSpec:
    """Network topology + relay tier (``repro.net``, DESIGN.md §15).

    ``topology`` picks the hearing graph restricting worker-to-worker
    overhearing (the paper's single-hop radio is ``complete``);
    ``degree`` parametrises ring / random_geometric; ``adjacency`` is
    the explicit graph's row string ("011;101;110"). ``relays`` > 0
    routes every uplink through a relay tier (``byz_relays`` of them
    Byzantine) with the ``broadcast`` discipline: ``direct`` trusts one
    forwarding relay, ``dolev`` sends over 2b+1 disjoint routes,
    ``bracha`` runs SEND/ECHO/READY reliable broadcast (needs
    relays >= 3*byz_relays + 1 to protect). The defaults are the
    paper's setup — no relays, everyone hears everyone.
    """

    topology: str = "complete"       # registry: topologies
    degree: int = 2                  # ring/random_geometric: hearing degree
    adjacency: str = ""              # explicit: "011;101;110" row string
    relays: int = 0                  # relay tier size (0 = single-hop)
    byz_relays: int = 0              # Byzantine relays in the tier
    broadcast: str = "direct"        # direct | dolev | bracha
    seed: int = 0                    # placement / relay PRNG seed


@dataclasses.dataclass(frozen=True)
class DataSpec:
    """What the workers sample gradients of.

    ``source="synthetic_lm"`` is the deterministic token stream
    (`repro.data`); ``source="quadratic"`` is the paper's numerical
    setting — a strongly-convex quadratic cost of dimension ``dim`` with
    conditioning mu/L and per-worker gradient noise ``noise``
    (Assumption 5), trained from ``w0 * ones(dim)``.
    """

    source: str = "synthetic_lm"     # synthetic_lm | quadratic
    seed: int = 0
    dim: int = 1000                  # quadratic: feature dimension
    mu: float = 0.5                  # quadratic: strong convexity
    L: float = 1.0                   # quadratic: smoothness
    noise: float = 1e-4              # quadratic: worker gradient noise
    w0: float = 2.0                  # quadratic: initial iterate scale


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    arch: str = "qwen3-0.6b"
    smoke: bool = False              # reduced() CPU-friendly variant
    param_dtype: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Worker topology. ``devices`` is how many devices the job uses:
    the first N chips, or under ``JAX_PLATFORMS=cpu`` that many host
    devices set before jax initialises; 0 uses every device."""

    devices: int = 8
    moe_impl: str = "tp"


@dataclasses.dataclass(frozen=True)
class ScenarioSpec:
    """The paper's sweep axes: who aggregates, who lies, how hard."""

    aggregator: str = "cgc"          # registry: collective_aggregators
    attack: str = "sign_flip"        # registry: attacks (trainer byz_mode)
    f: int = 0                       # aggregation resilience parameter
    n_byz: int = 0                   # simulated Byzantine workers
    echo_k: int = 4                  # echo-DP reference basis size
    echo_r: float = 0.9              # echo-DP deviation ratio (Eq. 7)
    data: DataSpec = DataSpec()
    comm: CommSpec = CommSpec()      # wire codec + broadcast channel
    net: NetSpec = NetSpec()         # hearing graph + relay tier


@dataclasses.dataclass(frozen=True)
class TrainSpec:
    strategy: str = "replicated"     # registry: train_strategies
    steps: int = 20
    batch: int = 8
    seq: int = 128
    optimizer: str = "adamw"         # adamw | sgd
    lr: float = 3e-4
    microbatches: int = 1
    clip_norm: float = 0.0
    log_every: int = 5
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 0
    resume: bool = False
    metrics_path: Optional[str] = None   # None: <run_dir>/metrics.jsonl
    # jax.profiler trace window: profile the first N fit rounds into
    # <run_dir>/profile (0 = off). A profile that cannot be taken fails
    # the run.
    profile_steps: int = 0


@dataclasses.dataclass(frozen=True)
class ServeSpec:
    requests: int = 8
    max_batch: int = 4
    page_size: int = 16
    num_pages: int = 128
    max_blocks_per_seq: int = 8
    prompt_len: int = 32
    gen: int = 32
    token_budget: int = 256
    decode_quantum: int = 8
    # SLO / redundancy knobs (DESIGN.md §11): prefill_chunk caps the
    # prompt tokens one lane prefills per step (0 = whole prompt at
    # once); prefix_cache enables cross-request CoW prefix sharing;
    # shared_prefix_len prepends that many common "system prompt"
    # tokens to every generated request; priority/deadline_s/tenants
    # set the submitted requests' scheduling class (deadline_s 0 =
    # none; tenants > 1 round-robins tenant labels).
    prefill_chunk: int = 0
    prefix_cache: bool = True
    shared_prefix_len: int = 0
    priority: int = 0
    deadline_s: float = 0.0
    tenants: int = 1
    seed: int = 0
    log_every: int = 5
    metrics_path: Optional[str] = None   # None: <run_dir>/metrics.jsonl
    sampling: SamplingSpec = SamplingSpec()


@dataclasses.dataclass(frozen=True)
class DryrunSpec:
    shape: str = "train_4k"
    variant: Optional[str] = None    # None: derived from train.strategy
    multi_pod: bool = False
    compile: bool = True
    out: str = "experiments/dryrun"


@dataclasses.dataclass(frozen=True)
class BenchSpec:
    """serve_bench trace shape: continuous batching vs fixed batches."""

    requests: int = 16
    batch: int = 4
    prompt_len: int = 8
    gen_short: int = 8
    gen_long: int = 128
    rate: float = 100.0              # Poisson arrival rate (req/s)
    page_size: int = 8
    num_pages: int = 64
    # shared-prefix leg: every request = shared_prefix_len common tokens
    # + a prompt_len unique tail, run with prefix sharing on vs off
    # (36 is deliberately NOT page_size-aligned so the divergent-tail
    # copy-on-write path runs in the standing record, not just tests)
    shared_prefix_len: int = 36
    prefill_chunk: int = 16
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class ObsSpec:
    """Observability: which tracker sink the run installs (DESIGN.md
    §12). ``jsonl`` (default) streams events/spans to ``events_path``
    (None: ``<run_dir>/events.jsonl``); ``noop`` disables tracking
    entirely; ``memory``/``stdout`` are for tests and debugging."""

    tracker: str = "jsonl"           # registry: trackers
    events_path: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """The root of the job tree — one serializable experiment."""

    name: str = "run"
    model: Optional[ModelSpec] = ModelSpec()
    mesh: MeshSpec = MeshSpec()
    scenario: ScenarioSpec = ScenarioSpec()
    train: Optional[TrainSpec] = None
    serve: Optional[ServeSpec] = None
    dryrun: Optional[DryrunSpec] = None
    bench: Optional[BenchSpec] = None
    obs: ObsSpec = ObsSpec()
    runs_root: str = "experiments/runs"

    # --- serialization ----------------------------------------------

    def to_json(self, indent: Optional[int] = 2) -> str:
        d: Dict[str, Any] = {"schema_version": SCHEMA_VERSION}
        d.update(dataclasses.asdict(self))
        return json.dumps(d, indent=indent, sort_keys=False)

    @classmethod
    def from_json(cls, text: str) -> "RunConfig":
        data = json.loads(text)
        if not isinstance(data, dict):
            raise ValueError("job config must be a JSON object")
        if "schema_version" not in data:
            raise ValueError(
                f"job config is missing 'schema_version' (current: "
                f"{SCHEMA_VERSION}) — required so future schema bumps "
                f"can't silently reinterpret old files")
        version = data.pop("schema_version")
        if version != SCHEMA_VERSION:
            raise ValueError(
                f"job config schema_version {version} != supported "
                f"{SCHEMA_VERSION}")
        return _from_dict(cls, data, path="")

    @classmethod
    def load(cls, path: str) -> "RunConfig":
        with open(path) as fh:
            return cls.from_json(fh.read())

    def save(self, path: str) -> None:
        with open(path, "w") as fh:
            fh.write(self.to_json() + "\n")


def config_hash(cfg: RunConfig) -> str:
    """Content hash of the canonical JSON form (run-dir naming)."""
    canon = json.dumps(json.loads(cfg.to_json()), sort_keys=True,
                       separators=(",", ":"))
    return hashlib.sha256(canon.encode()).hexdigest()


# ---------------------------------------------------------------------------
# Generic dataclass <-> dict machinery
# ---------------------------------------------------------------------------


def _unwrap_optional(tp) -> Tuple[Any, bool]:
    """Optional[X] -> (X, True); anything else -> (tp, False)."""
    if typing.get_origin(tp) is Union:
        args = [a for a in typing.get_args(tp) if a is not type(None)]
        if len(args) == 1:
            return args[0], True
    return tp, False


def _from_dict(cls, data: Dict[str, Any], path: str):
    if not isinstance(data, dict):
        raise ValueError(f"{path or cls.__name__}: expected an object, "
                         f"got {type(data).__name__}")
    hints = typing.get_type_hints(cls)
    names = {f.name for f in dataclasses.fields(cls)}
    unknown = set(data) - names
    if unknown:
        raise ValueError(
            f"unknown key(s) {sorted(unknown)} in "
            f"{path or 'job config'}; known: {sorted(names)}")
    kwargs = {}
    for f in dataclasses.fields(cls):
        if f.name not in data:
            continue
        sub = f"{path}.{f.name}" if path else f.name
        kwargs[f.name] = _coerce(hints[f.name], data[f.name], sub)
    return cls(**kwargs)


def _coerce(tp, value: Any, path: str):
    inner, optional = _unwrap_optional(tp)
    if value is None:
        if optional:
            return None
        raise ValueError(f"{path}: null is not allowed here")
    if dataclasses.is_dataclass(inner):
        return _from_dict(inner, value, path)
    if inner is float and isinstance(value, int) \
            and not isinstance(value, bool):
        return float(value)            # JSON writes 1.0 back as 1.0; a
                                       # hand-written 1 still means 1.0
    if inner is int and isinstance(value, bool):
        raise ValueError(f"{path}: expected int, got bool")
    if not isinstance(value, inner):
        raise ValueError(f"{path}: expected {inner.__name__}, "
                         f"got {type(value).__name__} ({value!r})")
    return value


# ---------------------------------------------------------------------------
# Dotted-path overrides: the CLI's --set train.steps=3
# ---------------------------------------------------------------------------


def _parse_leaf(tp, text: str, path: str):
    inner, optional = _unwrap_optional(tp)
    if optional and text.lower() in ("none", "null"):
        return None
    if inner is bool:
        if text.lower() in ("1", "true", "yes", "on"):
            return True
        if text.lower() in ("0", "false", "no", "off"):
            return False
        raise ValueError(f"{path}: expected a bool, got {text!r}")
    if inner in (int, float):
        try:
            return inner(text)
        except ValueError:
            raise ValueError(f"{path}: expected {inner.__name__}, "
                             f"got {text!r}") from None
    return text


def apply_overrides(cfg: RunConfig,
                    assignments: Sequence[str]) -> RunConfig:
    """Apply ``key.path=value`` edits to the frozen tree.

    Values coerce to the target field's type (``--set train.steps=3``
    yields an int; ``--set train.ckpt_dir=none`` clears an Optional).
    Setting into an absent Optional section instantiates its defaults
    first, so ``--set serve.max_batch=2`` works on a train-only job.
    """
    for item in assignments:
        if "=" not in item:
            raise ValueError(f"override {item!r} is not key.path=value")
        key, text = item.split("=", 1)
        cfg = _set_path(cfg, key.strip().split("."), text.strip(), key)
    return cfg


def _set_path(node, parts: List[str], text: str, full_key: str):
    if dataclasses.is_dataclass(node) and not isinstance(node, type):
        hints = typing.get_type_hints(type(node))
        names = {f.name for f in dataclasses.fields(type(node))}
        head = parts[0]
        if head not in names:
            raise ValueError(f"--set {full_key}: no field {head!r} on "
                             f"{type(node).__name__}; known: "
                             f"{sorted(names)}")
        tp = hints[head]
        if len(parts) == 1:
            inner, _ = _unwrap_optional(tp)
            if dataclasses.is_dataclass(inner):
                raise ValueError(
                    f"--set {full_key}: {head!r} is a section, not a "
                    f"leaf field — set one of its fields instead")
            value = _parse_leaf(tp, text, full_key)
        else:
            child = getattr(node, head)
            inner, _ = _unwrap_optional(tp)
            if not dataclasses.is_dataclass(inner):
                raise ValueError(f"--set {full_key}: {head!r} is a leaf "
                                 f"field, not a section")
            if child is None:
                child = inner()        # materialise the default section
            value = _set_path(child, parts[1:], text, full_key)
        return dataclasses.replace(node, **{head: value})
    raise ValueError(f"--set {full_key}: cannot descend into "
                     f"{type(node).__name__}")
