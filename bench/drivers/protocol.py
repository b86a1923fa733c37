"""Driver: the paper's Algorithm 1 through ``core.protocol.run_training``.

Each call is one episode of ``episode_rounds`` rounds from w0, with its
own key from the seed, jitted whole, so the window drives one device
program per episode with the same shapes every time. The program's own
``CommLedger`` folds each episode's trace in, as ``run_training`` does
when it is handed a ledger.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness
from bench.faults import patched
from bench.feeds import quadratic
from bench.reference import protocol as reference


def seed_key(seed: int) -> jax.Array:
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                              seed // 2 ** 32)


class Cell:
    def __init__(self, wl: Dict[str, Any], cfg: Dict[str, Any], seed: int,
                 devices):
        from repro.comm import resolve
        from repro.core import byzantine, theory
        from repro.core.types import ProtocolConfig
        from repro.run.config import CommSpec

        missing = reference.unsupported(cfg)
        if missing:
            raise harness.BenchError(
                f"{cfg['name']}: the plain reference does not implement "
                f"{', '.join(missing)}")
        self.wl, self.cfg, self.seed = wl, cfg, seed
        self.n, self.f, self.d = cfg["n"], cfg["f"], cfg["d"]
        self.mu = cfg["mu_over_L"] * cfg["L"]
        r, eta, _, _, _ = theory.pick_r_eta(
            self.n, self.f, cfg["L"], self.mu, cfg["sigma"],
            r_frac=cfg["r_frac"], eta_frac=cfg["eta_frac"])
        self.r, self.eta = float(r), float(eta)
        self.pcfg = ProtocolConfig(n=self.n, f=self.f, r=self.r,
                                   eta=self.eta)
        self.attack = byzantine.ATTACKS[cfg["attack"]]
        self.comm = resolve(CommSpec(channel=cfg["channel"],
                                     codec=cfg["codec"]))
        self.rounds = wl["episode_rounds"]
        self.precision = cfg["matmul_precision"]
        self.episode_fn = None
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        """Inputs and keys of ``seed``, and an empty record."""
        from repro.comm import CommLedger

        self.seed = seed
        self.key_cost, self.key_ep = jax.random.split(seed_key(seed))
        self.eigs = self.w_star = None
        self.ledger = CommLedger()
        self.episodes = 0
        self.finals: List[jax.Array] = []
        self.traces: List[Dict[str, np.ndarray]] = []
        self.n_echo = 0
        self.wire = None

    def prepare(self) -> None:
        with self._precision():
            self.eigs, self.w_star = jax.jit(
                quadratic.arrays, static_argnums=(1, 2, 3))(
                self.key_cost, self.d, self.mu, self.cfg["L"])

    def _precision(self):
        return jax.default_matmul_precision(self.precision)

    def build(self):
        from repro.core.protocol import run_training

        n, d, cfg = self.n, self.d, self.cfg
        byz = jnp.arange(n) < cfg["n_byz"]

        def episode(key, eigs, w_star):
            cost = quadratic.cost(eigs, w_star, self.mu, cfg["L"],
                                  cfg["sigma"])
            w0 = jnp.full((d,), cfg["w0"], jnp.float32)
            return run_training(self.pcfg, cost, self.attack, byz, key, w0,
                                rounds=self.rounds,
                                aggregator=cfg["aggregator"],
                                comm=self.comm)

        return jax.jit(episode)

    def setup(self) -> None:
        self.prepare()
        with self._precision():
            self.episode_fn = self.build()
            for i in range(self.wl["warmup_episodes"]):
                jax.block_until_ready(self.episode_fn(
                    jax.random.fold_in(self.key_ep, 2 ** 30 + i), self.eigs,
                    self.w_star))

    def step(self) -> int:
        key = jax.random.fold_in(self.key_ep, self.episodes)
        with harness.annotate("bench.round"), self._precision():
            tr = self.episode_fn(key, self.eigs, self.w_star)
            jax.block_until_ready(tr)
        with harness.annotate("bench.ledger"):
            host = {k: np.asarray(tr[k]) for k in
                    ("bits", "n_echo", "n_detected", "dist2")}
            self.ledger.record_protocol_trace(host, self.n, self.d,
                                              self.comm.codec)
        self.finals.append(tr["w_final"])
        self.traces.append(host)
        self.n_echo += int(host["n_echo"].sum())
        self.episodes += 1
        if self.wire is None and self.ledger.rounds >= \
                self.wl["wire_rounds"]:
            self.wire = self.ledger.bits_sent / self.ledger.bits_baseline
        return self.rounds

    def failed(self) -> int:
        """Rounds whose distance to the optimum is not finite."""
        return int(sum((~np.isfinite(t["dist2"])).sum()
                       for t in self.traces))

    def counters(self) -> Dict[str, Any]:
        rounds = self.episodes * self.rounds
        return {"rounds": rounds, "slots": rounds * self.n,
                "n_echo": self.n_echo, "wire_bits_ratio": self.wire,
                "n": self.n, "d": self.d}

    def _reference(self, episode: int, dtype=jnp.float32):
        return reference.episode(
            jax.random.fold_in(self.key_ep, episode), self.eigs, self.w_star,
            n=self.n, f=self.f, r=self.r, eta=self.eta,
            sigma=self.cfg["sigma"], mu=self.mu, L=self.cfg["L"],
            n_byz=self.cfg["n_byz"], w0=self.cfg["w0"], rounds=self.rounds,
            dtype=dtype)

    def check(self) -> List[Dict[str, Any]]:
        """The sampled episodes against the plain reference."""
        rng = np.random.default_rng(self.seed)
        picks = sorted(rng.choice(self.episodes, replace=False,
                                  size=min(self.wl["check_episodes"],
                                           self.episodes)).tolist())
        got = [(i, np.asarray(self.finals[i]), self.traces[i]["bits"])
               for i in picks]
        self.finals = []
        return self.compare(got)

    def reading(self, seed: int, variant: str) -> List[Dict[str, Any]]:
        """The compared numbers of one episode of ``seed``: the program's
        (a planted fault is active around the call), or with ``control``
        the reference in bfloat16 in the program's place."""
        self.reseed(seed)
        self.prepare()
        if variant == "control":
            return self.control()
        if self.episode_fn is None:
            self.episode_fn = self.build()
        self.step()
        return self.check()

    def control(self) -> List[Dict[str, Any]]:
        """The reference in bfloat16 put in the program's place, for the
        first episode of the seed."""
        lo = self._reference(0, jnp.bfloat16)
        return self.compare([(0, np.asarray(lo["w_final"]),
                              np.asarray(lo["bits"], np.float64))])

    def compare(self, got) -> List[Dict[str, Any]]:
        """``got``: (episode, final w, per-round bits) of what is checked."""
        w0 = np.full(self.d, self.cfg["w0"], np.float32)
        w_gap = bits_gap = 0.0
        for i, w_got, bits in got:
            ref = self._reference(i)
            w_ref = np.asarray(ref["w_final"], np.float64)
            w_gap = max(w_gap, float(
                np.linalg.norm(w_got - w_ref) / np.linalg.norm(w_ref - w0)))
            bits_ref = np.asarray(ref["bits"], np.float64)
            bits_gap = max(bits_gap, float(np.max(
                np.abs(np.asarray(bits, np.float64) - bits_ref) / bits_ref)))
        limits = self.wl["limits"]
        return [{"name": "w_gap", "value": w_gap, "limit": limits["w_gap"]},
                {"name": "bits_gap", "value": bits_gap,
                 "limit": limits["bits_gap"]}]

    def costs(self) -> Dict[str, float]:
        from bench.costs import protocol as pc
        return {"round_flops": pc.round_flops(self.n, self.d),
                "round_bytes": pc.round_bytes(self.n, self.d),
                "cgc_flops": pc.cgc_flops(self.n, self.d),
                "cgc_bytes": pc.cgc_bytes(self.n, self.d)}


def _unchanged():
    from repro.core import protocol

    def make(orig):
        def echo_cgc_round(cfg, w, *a, **k):
            return (w,) + tuple(orig(cfg, w, *a, **k)[1:])
        return echo_cgc_round

    return patched(protocol, "echo_cgc_round", make)


def _half_batch():
    from repro.core import protocol
    from repro.core.types import ServerState

    def make(orig):
        def aggregate(server, f, aggregator="cgc"):
            n = server.G.shape[0]
            rest = ServerState(*(x[n // 2:] for x in server))
            return 2.0 * orig(rest, f, aggregator)
        return aggregate

    return patched(protocol, "aggregate", make)


def _altered():
    """Every honest worker's echo decision comes out "raw"."""
    from repro.core import protocol

    def make(orig):
        def decide(*a, **k):
            dec = orig(*a, **k)
            return dec._replace(send_echo=jnp.zeros_like(dec.send_echo))
        return decide

    return patched(protocol, "echo_decision_from_projection", make)


# the exchange between chips is no fault of a one-chip cell
FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "altered": _altered}
