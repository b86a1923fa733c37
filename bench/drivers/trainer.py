"""Driver: ``launch.engine.Trainer.run_round`` on a decoder from its config.

The Trainer is built as ``repro.run.facade.train`` builds it, with the
weights made from the seed instead of the program's own init. Set-up
builds one Trainer and one state, drives them through the first
``check_steps`` rounds on the cell's feed (these are what the plain
reference follows), warms up, and hands the same Trainer and state to the
window. After the window the state is freed and the reference runs.
"""
from __future__ import annotations

import gc
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness
from bench.faults import patched
from bench.feeds import lm
from bench.reference import qwen3 as reference


# what the driver builds and the plain reference computes: a Qwen3 decoder
# (GQA with RMSNorm on queries and keys, SwiGLU, no attention bias)
IMPLEMENTS = {"model_type": "qwen3", "hidden_act": "silu",
              "attention_bias": False}


def model_config(cfg: Dict[str, Any], param_dtype: str | None = None):
    """The program's ModelConfig with the sizes of ``cfg``; a config of
    another kind is refused, not run as this one."""
    from repro.configs.base import ModelConfig

    missing = [f"{k}={cfg.get(k)!r}" for k, v in IMPLEMENTS.items()
               if cfg.get(k) != v]
    if missing:
        raise harness.BenchError(f"{cfg['name']}: the driver and the "
                                 f"reference do not implement "
                                 f"{', '.join(missing)}")
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        attn_type="gqa", head_dim=cfg["head_dim"], qk_norm=True,
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["compute_dtype"],
        param_dtype=param_dtype or cfg["param_dtype"])


@jax.jit
def _norms(tree):
    return reference.leaf_norms(tree)


class Cell:
    def __init__(self, wl: Dict[str, Any], cfg: Dict[str, Any], seed: int,
                 devices):
        self.wl, self.cfg, self.seed = wl, cfg, seed
        self.param_dtype = cfg["param_dtype"]
        model_config(cfg)
        self.B, self.S = wl["batch"], wl["seq"]
        self.trainer = None
        self.state = None
        self.rounds = 0
        self.losses: List[float] = []

    # --- the program ---------------------------------------------------

    def build(self):
        from repro.launch.engine import Trainer, TrainerConfig, TrainSettings
        from repro.optim import adamw

        o = self.wl["optimizer"]
        settings = TrainSettings(aggregator=self.wl["aggregator"])
        # one chip: one worker, so the facade builds no mesh
        return Trainer(self.wl["strategy"],
                       model_config(self.cfg, self.param_dtype),
                       adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"]),
                       settings, None, self.B,
                       TrainerConfig(log_every=1 << 30),
                       printer=lambda line: None)

    def _feed(self, step: int):
        return lm.batch(self.data_key, step, self.cfg["vocab_size"], self.B,
                        self.S)

    def begin(self, seed: int) -> None:
        """A fresh state from ``seed`` driven through the checked rounds."""
        self.seed = seed
        key = lm.seed_key(seed)
        self.weight_key, self.data_key = jax.random.split(key)
        values = lm.weights(self.weight_key, self.cfg)
        if self.param_dtype != "float32":
            values = jax.tree.map(lambda x: x.astype(self.param_dtype),
                                  values)
        state = self.trainer.init_state(values)
        del values
        steps = self.wl["check_steps"]
        self.prog_losses, self.prog_grads = [], None
        for s in range(steps):
            state, rec = self.trainer.run_round(state, self._feed(s))
            self.prog_losses.append(rec["loss"])
            if s == 0:
                # AdamW's first moment after one step is (1 - b1) g
                b1 = self.wl["optimizer"]["b1"]
                self.prog_grads = [float(x) / (1 - b1) for x in
                                   _norms(state.opt_state.mu)]
        self.prog_delta = reference.delta_norms(
            jax.tree.map(lambda x: x.astype(jnp.float32), state.values),
            self.weight_key, self.cfg)
        self.state = state
        self.fed = steps

    def setup(self) -> None:
        self.trainer = self.build()
        self.begin(self.seed)
        for _ in range(self.wl["warmup_rounds"]):
            self._round()
        self.rounds = 0
        self.losses = []

    def _round(self) -> None:
        with harness.annotate("bench.data"):
            batch = self._feed(self.fed)
        with harness.annotate("bench.round"):
            self.state, rec = self.trainer.run_round(self.state, batch)
        self.fed += 1
        self.losses.append(rec["loss"])

    def step(self) -> int:
        self._round()
        self.rounds += 1
        return 1

    def failed(self) -> int:
        return int(np.sum(~np.isfinite(self.losses)))

    def counters(self) -> Dict[str, Any]:
        return {"rounds": self.rounds, "tokens_per_round": self.B * self.S,
                "seq": self.S}

    def costs(self) -> Dict[str, float]:
        from bench.costs import qwen3 as qc
        return {"train_flops_per_token":
                3 * qc.fwd_flops_per_token(self.cfg, self.S / 2)}

    # --- the check -------------------------------------------------------

    def reading(self, seed: int, variant: str) -> List[Dict[str, Any]]:
        """The compared numbers of ``seed``'s checked rounds: the
        program's (a planted fault is active around the call), or with
        ``control`` the program's own path below the float32 master
        weights the configuration states (weights in bfloat16)."""
        if variant == "control" and self.param_dtype != "bfloat16":
            self.param_dtype, self.trainer = "bfloat16", None
        if self.trainer is None:
            self.trainer = self.build()
        self.begin(seed)
        return self.check()

    def release(self) -> None:
        self.state = None
        gc.collect()

    def check(self) -> List[Dict[str, Any]]:
        """The checked rounds against the plain reference."""
        self.release()
        o = self.wl["optimizer"]
        ref = reference.train_steps(self.cfg, self.weight_key, self.data_key,
                                    o, self.wl["check_steps"], self.B,
                                    self.S)
        return compare(self.prog_losses, self.prog_grads, self.prog_delta,
                       ref, self.wl["limits"])


def gap_by_leaf(prog: List[float], ref: List[float],
                keep: List[bool]) -> float:
    """Worst leaf's |norm - reference norm| over the larger of that
    leaf's reference norm and the median leaf's."""
    ref_a, prog_a = np.asarray(ref), np.asarray(prog)
    floor = np.median(ref_a)
    gaps = np.abs(prog_a - ref_a) / np.maximum(ref_a, floor)
    return float(np.max(gaps[np.asarray(keep)]))


def compare(losses, grads, delta, ref, limits) -> List[Dict[str, Any]]:
    rl = np.asarray(ref["losses"])
    loss_gap = float(np.max(np.abs(np.asarray(losses) - rl) / np.abs(rl)))
    rg = np.asarray(ref["grad_norms"])
    # a leaf whose reference gradient is nought to rounding moves under
    # Adam by round-off alone: it is left out of the change
    moving = list(rg >= 1e-3 * np.median(rg))
    return [
        {"name": "loss_gap", "value": loss_gap, "limit": limits["loss_gap"]},
        {"name": "grad_gap", "value": gap_by_leaf(grads, ref["grad_norms"],
                                                  [True] * len(rg)),
         "limit": limits["grad_gap"]},
        {"name": "delta_gap", "value": gap_by_leaf(delta, ref["delta_norms"],
                                                   moving),
         "limit": limits["delta_gap"]},
    ]


def _unchanged():
    from repro.launch import engine

    def make(orig_init):
        def init(self, *a, **k):
            orig_init(self, *a, **k)
            fn = self.bundle.fn
            self.step_fn = jax.jit(
                lambda v, o, b, s: (v, o) + tuple(fn(v, o, b, s)[2:]))
        return init

    return patched(engine.Trainer, "__init__", make)


def _half_batch():
    from repro.models import model

    def make(orig):
        def train_loss(v, cfg, batch, shard_ctx=None):
            half = {k: x[: x.shape[0] // 2] for k, x in batch.items()}
            return orig(v, cfg, half, shard_ctx)
        return train_loss

    return patched(model, "train_loss", make)


# the exchange between chips is no fault of a one-chip cell, and a token
# altered where it is produced is the loss's to catch, as half_batch is
FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch}
