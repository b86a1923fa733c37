"""Plain reference of one Echo-CGC episode (Algorithm 1), independent of
the program.

The same inputs as the program: the quadratic of ``bench.feeds`` and the
gradient draws ``run_training`` documents (round keys split from the
episode key, worker j's key the j-th of n+1 split from the round key).
The method is its own: span(R) is kept as an orthonormal basis built by
Gram-Schmidt (twice, for stability) instead of the program's ridged
normal equations, so an echo's projection is Q^T Q g. The server's
reconstruction k * A x of the paper is that projection scaled back to
||g||. Bits are counted on the host in exact integers from the decisions.
Float32 with every matmul at 'highest'. It implements one setting of
each of the configuration's choices (``IMPLEMENTS``); a configuration
that states another is refused before any run, not compared with the
wrong thing.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.feeds import quadratic

# the settings this reference computes: sign-flipping Byzantine workers,
# the CGC filter, a channel that delivers every slot, fp32 on the wire
IMPLEMENTS = {"attack": "sign_flip", "aggregator": "cgc",
              "channel": "ideal", "codec": "fp32"}


def unsupported(cfg: Dict[str, Any]):
    """``key=value`` of each setting of ``cfg`` this reference lacks."""
    return [f"{k}={cfg.get(k)!r}" for k, v in IMPLEMENTS.items()
            if cfg.get(k) != v]


def _round(key, w, eigs, w_star, *, n, f, r, eta, sigma, mu, L, n_byz,
           indep_tol):
    dtype = w.dtype
    cost = quadratic.cost(eigs, w_star, mu, L, sigma)
    d = w.shape[0]
    keys = jax.random.split(key, n + 1)
    honest = jax.vmap(lambda k: cost.stoch_grad(k, w))(keys[:n]).astype(dtype)
    byz = jnp.arange(n) < n_byz
    msgs = jnp.where(byz[:, None], -honest, honest)    # sign_flip

    def slot(i, st):
        Q, cnt, G, echo, rank = st
        g = msgs[i]
        p = (Q @ g) @ Q
        gn = jnp.sqrt(jnp.sum(g * g))
        pn = jnp.sqrt(jnp.sum(p * p))
        res = jnp.sqrt(jnp.sum((p - g) ** 2))
        e = (~byz[i]) & (cnt > 0) & (res <= r * gn) & (pn > 0)
        G = G.at[i].set(jnp.where(e, (gn / jnp.maximum(pn, 1e-30)) * p, g))
        add = (~e) & ((res > indep_tol * gn) | (cnt == 0))
        v = g - p
        v = v - (Q @ v) @ Q
        q = v / jnp.maximum(jnp.sqrt(jnp.sum(v * v)), 1e-30)
        row = jnp.minimum(cnt, n - 1)
        Q = Q.at[row].set(jnp.where(add, q, Q[row]))
        return (Q, cnt + add.astype(jnp.int32), G, echo.at[i].set(e),
                rank.at[i].set(cnt))

    st = (jnp.zeros((n, d), dtype), jnp.int32(0),
          jnp.zeros((n, d), dtype), jnp.zeros((n,), bool),
          jnp.zeros((n,), jnp.int32))
    _, _, G, echo, rank = jax.lax.fori_loop(0, n, slot, st)
    norms = jnp.sqrt(jnp.sum(G * G, axis=1))
    thr = jnp.sort(norms)[n - f - 1]
    scales = jnp.minimum(1.0, thr / jnp.maximum(norms, 1e-12))
    agg = jnp.sum(G * scales[:, None].astype(dtype), axis=0)
    return (w - eta * agg).astype(dtype), echo, rank


def episode(key, eigs, w_star, *, n: int, f: int, r: float, eta: float,
            sigma: float, mu: float, L: float, n_byz: int, w0: float,
            rounds: int, indep_tol: float = 1e-6,
            dtype=jnp.float32) -> Dict[str, Any]:
    """One episode of ``rounds`` rounds from w0: the final w, and per
    round the echo count and the exact bits all workers sent. ``dtype``
    below float32 makes the control: the same method in less precision."""
    d = w_star.shape[0]
    eigs, w_star = eigs.astype(dtype), w_star.astype(dtype)
    with jax.default_matmul_precision("highest"):
        step = jax.jit(partial(_round, n=n, f=f, r=r, eta=eta, sigma=sigma,
                               mu=mu, L=L, n_byz=n_byz,
                               indep_tol=indep_tol))
        w = jnp.full((d,), w0, dtype)
        bits, n_echo = [], []
        for k in jax.random.split(key, rounds):
            w, echo, rank = step(k, w, eigs, w_star)
            echo, rank = np.asarray(echo), np.asarray(rank)
            n_echo.append(int(echo.sum()))
            # fp32 wire: a raw message is d floats, an echo (k, x, I) is
            # 1 + |R| floats and an n-bit membership bitmap
            bits.append(sum(32 * (1 + int(k_)) + n if e else 32 * d
                            for e, k_ in zip(echo, rank)))
    return {"w_final": w.astype(jnp.float32), "bits": bits,
            "n_echo": n_echo}
