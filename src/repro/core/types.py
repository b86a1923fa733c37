"""Core datatypes for the Echo-CGC protocol.

Everything is a pytree of fixed-shape jnp arrays so the whole round is
jittable. The radio network is simulated with dense buffers + masks:

- gradients are stored row-major ``(n, d)``;
- the overheard raw-gradient set ``R`` is the same ``(n, d)`` buffer with a
  boolean column mask (a worker's view is a prefix of the slot order);
- messages are tagged unions encoded by ``kind`` flags.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

# Message kinds + the paper's float width now live with the wire formats
# (repro.comm.wire) and are re-exported here for the protocol buffers.
from repro.comm.wire import (BITS_PER_FLOAT, FP32, MSG_ECHO,  # noqa: F401
                             MSG_RAW, MSG_SILENT)


class RoundMessages(NamedTuple):
    """Everything broadcast during one communication phase (n slots)."""

    kind: jax.Array          # (n,) int32 in {MSG_RAW, MSG_ECHO, MSG_SILENT}
    raw: jax.Array           # (n, d) raw gradient per slot (valid iff kind==RAW)
    echo_k: jax.Array        # (n,)   norm ratio ||g||/||Ax||  (valid iff ECHO)
    echo_x: jax.Array        # (n, n) projection coefficients, masked by echo_ref
    echo_ref: jax.Array      # (n, n) bool, echo_ref[j, i] = echo of j references worker i


class ServerState(NamedTuple):
    """Parameter-server view after the communication phase."""

    G: jax.Array             # (n, d) reconstructed gradients (0 for detected Byz)
    received: jax.Array      # (n,) bool, server heard slot j
    detected: jax.Array      # (n,) bool, provably Byzantine (bad echo reference)


class RoundStats(NamedTuple):
    """Per-round accounting used for the paper's communication analysis."""

    bits_sent: jax.Array         # (n,) bits transmitted by each worker
    echo_sent: jax.Array         # (n,) bool, worker echoed instead of raw
    n_echo: jax.Array            # () int32, number of echo messages
    n_detected: jax.Array        # () int32, Byzantine workers caught by server
    rank_R: jax.Array            # () int32, final size of the reference set
    n_faded: Any = None          # () int32, slots the channel faded this round
                                 # (None from pre-channel call sites)
    proj_rows: Any = None        # () int32, reference rows the round's slot
                                 # projections read, summed over slots
    recon_rows: Any = None       # () int32, table rows the server's echo
                                 # reconstructions read, summed over slots


class ProtocolConfig(NamedTuple):
    """Static protocol parameters (hashable; safe as jit static arg)."""

    n: int                   # number of workers
    f: int                   # max tolerable Byzantine workers
    r: float                 # deviation ratio (Eq. 7)
    eta: float               # step size
    indep_tol: float = 1e-6  # relative residual below which a raw gradient is
                             # considered linearly dependent (App. D test)
    ridge: float = 1e-8      # Tikhonov term for the Gram solve (numerical MP-inverse)


def raw_bits(d: int) -> int:
    """Bits to broadcast a raw gradient: d floats (paper Sec. 2.1).

    Delegates to the ideal fp32 codec — ``repro.comm.wire`` owns the
    wire-format bit accounting; this closed form is the fp32 special
    case kept for the paper-facing call sites.
    """
    return FP32.raw_msg_bits(d)


def echo_bits(n: int, rank: jax.Array | int) -> jax.Array | int:
    """Bits for an echo message ``(k, x, I)``.

    One float for the norm ratio, ``|R|`` floats for the coefficients, and an
    n-bit membership bitmap for the sorted ID list ``I`` (an upper bound on
    any practical encoding of I; O(n) total as in the paper). Delegates to
    the ideal fp32 codec in ``repro.comm.wire``.
    """
    return FP32.echo_msg_bits(n, rank)
