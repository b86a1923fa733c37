"""The paper's quadratic cost (Sec. 4.3), made from a seed on the device.

Q(w) = 1/2 (w - w*)^T H (w - w*) with spec(H) in [mu, L], and a
stochastic oracle ``g = grad + sigma ||grad|| u`` with u ~ N(0, I)/sqrt(d),
so that E||g - grad||^2 = sigma^2 ||grad||^2 (Assumption 5 with equality).

H is diagonal: the protocol and the noise are rotation invariant, so a
random rotation changes nothing but the cost of holding a dense d x d
matrix, which no chip can hold at the d the benchmark runs. At the
headline mu/L = 1 every eigenvalue is 1 and H is the identity.

``cost(eigs, w_star, ...)`` has the attributes that
``core.protocol.run_training`` reads (``stoch_grad``, ``grad``, ``value``,
``w_star``, ``L``, ``mu``). It is built inside the jitted episode from
array arguments, so no (d,) constant is baked into a compiled program,
and the plain reference builds the same object from the same arrays.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Quadratic:
    value: Callable
    grad: Callable
    stoch_grad: Callable
    w_star: jax.Array
    L: float
    mu: float
    sigma: float
    d: int


def arrays(key: jax.Array, d: int, mu: float, L: float):
    """(eigs, w_star): the spectrum, both ends hit exactly, and the optimum."""
    k_eig, k_star = jax.random.split(key)
    inner = jax.random.uniform(k_eig, (d - 2,), minval=mu, maxval=L)
    eigs = jnp.concatenate([jnp.array([mu, L], jnp.float32), inner])
    return eigs, jax.random.normal(k_star, (d,))


def cost(eigs: jax.Array, w_star: jax.Array, mu: float, L: float,
         sigma: float) -> Quadratic:
    d = w_star.shape[-1]

    def grad(w):
        return eigs * (w - w_star)

    def value(w):
        dw = w - w_star
        return 0.5 * jnp.sum(eigs * dw * dw)

    def stoch_grad(key, w):
        g = grad(w)
        u = jax.random.normal(key, (d,)) / jnp.sqrt(d)
        return g + sigma * jnp.sqrt(jnp.sum(g * g)) * u

    return Quadratic(value, grad, stoch_grad, w_star, float(L), float(mu),
                     float(sigma), d)
