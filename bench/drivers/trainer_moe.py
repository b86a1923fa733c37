"""Driver: ``launch.engine.Trainer.run_round`` on one chip's expert-parallel
share of a DeepSeek-V2 decoder (MoE + MLA with YaRN) from its config.

The trainer driver's cell (``bench/drivers/trainer.py``) with this
model's config, weights (``bench/feeds/deepseek_v2.py``), plain reference
(``bench/reference/deepseek_v2.py``), FLOP count and faults. The layer
holds routed experts [expert_offset, expert_offset + n_routed_experts) of
``n_routed_experts_published`` and routes over all of them, dropless.
Token ids are uniform over the vocabulary slice (``feed.batch`` says why).
The MoE counters of each window round (held copies, the worst layer's
largest held expert over the held mean, dropped copies) come out of the
step with its loss.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness
from bench.drivers import trainer
from bench.faults import patched
from bench.feeds import deepseek_v2 as feed
from bench.reference import deepseek_v2 as reference

# what the driver builds and the plain reference computes: DeepSeek-V2
# (MLA without query LoRA, YaRN, softmax greedy top-k routing over one
# group, shared experts, a sequence-wise aux loss, an untied head)
IMPLEMENTS = {"model_type": "deepseek_v2", "hidden_act": "silu",
              "attention_bias": False, "q_lora_rank": None,
              "scoring_func": "softmax", "topk_method": "greedy",
              "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
              "seq_aux": True, "tie_word_embeddings": False}


def model_config(cfg: Dict[str, Any], param_dtype: str | None = None):
    """The program's ModelConfig with the sizes of ``cfg``; a config of
    another kind is refused, not run as this one."""
    from repro.configs.base import ModelConfig

    missing = [f"{k}={cfg.get(k)!r}" for k, v in IMPLEMENTS.items()
               if cfg.get(k) != v]
    rs = cfg.get("rope_scaling") or {}
    if rs.get("type") != "yarn":
        missing.append(f"rope_scaling={rs!r}")
    if missing:
        raise harness.BenchError(f"{cfg['name']}: the driver and the "
                                 f"reference do not implement "
                                 f"{', '.join(missing)}")
    try:
        return _model_config(ModelConfig, cfg, rs, param_dtype)
    except TypeError as e:       # a program without these fields
        raise harness.BenchError(f"{cfg['name']}: the program cannot build "
                                 f"this model: {e}") from e


def _model_config(ModelConfig, cfg, rs, param_dtype):
    return ModelConfig(
        name=cfg["name"], family="moe",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        attn_type="mla", head_dim=cfg["v_head_dim"], q_lora_rank=0,
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        yarn_factor=float(rs["factor"]),
        yarn_original_max_pos=rs["original_max_position_embeddings"],
        yarn_beta_fast=float(rs["beta_fast"]),
        yarn_beta_slow=float(rs["beta_slow"]),
        yarn_mscale=float(rs["mscale"]),
        yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
        num_experts=cfg["n_routed_experts_published"],
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg["expert_offset"],
        num_shared_experts=cfg["n_shared_experts"],
        top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        first_dense_layers=cfg["first_k_dense_replace"],
        router_aux="seq", router_aux_coef=cfg["aux_loss_alpha"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_eps=cfg["rms_norm_eps"], tie_embeddings=False,
        dtype=cfg["compute_dtype"],
        param_dtype=param_dtype or cfg["param_dtype"])


@jax.jit
def _norms(tree):
    return reference.leaf_norms(tree), reference.expert_norms(tree)


class Cell(trainer.Cell):
    def __init__(self, wl, cfg, seed, devices):
        self.wl, self.cfg, self.seed = wl, cfg, seed
        self.param_dtype = cfg["param_dtype"]
        model_config(cfg)
        self.B, self.S = wl["batch"], wl["seq"]
        self.trainer = None
        self.state = None
        self.rounds = 0
        self.losses: List[float] = []
        self.moe: Dict[str, List[float]] = {}

    def build(self):
        from repro.launch.engine import Trainer, TrainerConfig, TrainSettings
        from repro.optim import adamw

        o = self.wl["optimizer"]
        settings = TrainSettings(aggregator=self.wl["aggregator"])
        return Trainer(self.wl["strategy"],
                       model_config(self.cfg, self.param_dtype),
                       adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"]),
                       settings, None, self.B,
                       TrainerConfig(log_every=1 << 30),
                       printer=lambda line: None)

    def _feed(self, step: int):
        return feed.batch(self.data_key, step, self.cfg["vocab_size"],
                          self.B, self.S)

    def begin(self, seed: int) -> None:
        """A fresh state from ``seed`` driven through the checked rounds."""
        from bench.feeds import lm

        self.seed = seed
        self.weight_key, self.data_key = jax.random.split(lm.seed_key(seed))
        values = feed.weights(self.weight_key, self.cfg)
        if self.param_dtype != "float32":
            values = jax.tree.map(lambda x: x.astype(self.param_dtype),
                                  values)
        state = self.trainer.init_state(values)
        del values
        self.prog_losses, self.prog_grads = [], None
        for s in range(self.wl["check_steps"]):
            state, rec = self.trainer.run_round(state, self._feed(s))
            self.prog_losses.append(rec["loss"])
            if s == 0:
                # AdamW's first moment after one step is (1 - b1) g
                b1 = self.wl["optimizer"]["b1"]
                leaves, slabs = _norms(state.opt_state.mu)
                self.prog_grads = [float(x) / (1 - b1) for x in leaves]
                self.prog_experts = [float(x) / (1 - b1) for x in slabs]
        self.prog_delta = reference.delta_norms(
            jax.tree.map(lambda x: x.astype(jnp.float32), state.values),
            self.weight_key, self.cfg)
        self.state = state
        self.fed = self.wl["check_steps"]

    def setup(self) -> None:
        super().setup()
        self.moe = {}

    def _round(self) -> None:
        with harness.annotate("bench.data"):
            batch = self._feed(self.fed)
        with harness.annotate("bench.round"):
            self.state, rec = self.trainer.run_round(self.state, batch)
        self.fed += 1
        self.losses.append(rec["loss"])
        for k in ("moe_held_copies", "moe_load_ratio", "moe_dropped"):
            self.moe.setdefault(k, []).append(rec[k])

    def counters(self) -> Dict[str, Any]:
        c = super().counters()
        for k, v in self.moe.items():
            c[k] = float(np.mean(v))
        return c

    def costs(self) -> Dict[str, float]:
        from bench.costs import deepseek_v2 as dc

        copies = self.counters().get("moe_held_copies")
        if copies is None:
            return {}
        tokens = self.B * self.S
        return {"train_flops_per_token": 3 * dc.fwd_flops_per_token(
                    self.cfg, self.S / 2, copies / tokens),
                "experts_flops_per_round": dc.experts_flops_executed(
                    self.cfg, copies),
                "experts_bytes_per_round": dc.experts_bytes_executed(
                    self.cfg, copies)}

    def check(self) -> List[Dict[str, Any]]:
        """The checked rounds against the plain reference."""
        self.release()
        o = self.wl["optimizer"]
        ref = reference.train_steps(self.cfg, self.weight_key, self.data_key,
                                    o, self.wl["check_steps"], self.B,
                                    self.S)
        return compare(self.prog_losses, self.prog_grads, self.prog_experts,
                       self.prog_delta, ref, self.wl["limits"])


def compare(losses, grads, experts, delta, ref, limits
            ) -> List[Dict[str, Any]]:
    """The trainer cell's numbers and ``expert_grad_gap``: the worst
    (layer, held expert) slab's gap of first-gradient norms, so that a
    fault in some experts' share of the copies is not averaged away over
    a stacked leaf."""
    return trainer.compare(losses, grads, delta, ref, limits) + [
        {"name": "expert_grad_gap",
         "value": trainer.gap_by_leaf(experts, ref["expert_grad_norms"],
                                      [True] * len(experts)),
         "limit": limits["expert_grad_gap"]}]


def _capacity_drop():
    """The held experts computed as a capacity dispatch does: in each
    expert, the copies past T k / E x 1.25 (in token order) add nothing."""
    from repro.models import moe

    def make(orig):
        def moe_held(p, cfg, x, top_w, top_i, offset=None):
            T, k = top_i.shape
            E = cfg.num_experts
            C = int(max(8, round(T * k / E * 1.25)))
            flat = top_i.reshape(-1)
            order = jnp.argsort(flat, stable=True)
            counts = jnp.zeros((E,), jnp.int32).at[flat].add(1)
            starts = jnp.cumsum(counts) - counts
            # each copy's place among its expert's copies
            pos = jnp.zeros_like(flat).at[order].set(
                jnp.arange(T * k) - starts[flat[order]])
            top_w = jnp.where((pos < C).reshape(T, k), top_w, 0.0)
            return orig(p, cfg, x, top_w, top_i, offset)
        return moe_held

    return patched(moe, "moe_held", make)


def _normalised_topk():
    """The top-k weights renormalised to sum to 1."""
    from repro.models import moe

    def make(orig):
        def router_topk(logits, top_k, normalise=True, scaling=1.0):
            return orig(logits, top_k, True, scaling)
        return router_topk

    return patched(moe, "router_topk", make)


@contextlib.contextmanager
def _plain_rope():
    """Plain RoPE frequencies and the plain softmax scale: no YaRN."""
    from repro.models import attention

    with patched(attention, "rope_spec",
                 lambda orig: lambda cfg, dim: (None, 1.0)), \
            patched(attention, "mla_scale", lambda orig: lambda cfg: None):
        yield


FAULTS = {"unchanged": trainer._unchanged, "half_batch": trainer._half_batch,
          "capacity_drop": _capacity_drop,
          "normalised_topk": _normalised_topk, "plain_rope": _plain_rope}
