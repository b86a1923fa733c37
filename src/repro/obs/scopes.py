"""Names of the device scopes at the program's layer boundaries.

Each is a ``jax.named_scope``: it changes nothing but the ``op_name``
metadata of the operations traced inside it, which the TPU profiler
reports as each op's ``tf_op`` path (``jit(episode)/while/body/
protocol.slot/decide/echo.project/dot_general``). A reader of a device
trace sums the time of the ops whose path holds a scope, so the number
follows the work through any change that renames the compiled ops. The
names live here once, for the code that opens the scopes and for the
tests and trace readers that look for them.
"""
from __future__ import annotations

# core/echo.py: projection (A^T g, the masked Gram, given or built, the
# solve, echo)
ECHO_PROJECT = "echo.project"
# dist/echo_dp.py: the (K, K) Gram of the echo-DP reference basis
ECHO_BASIS_GRAM = "echo.basis_gram"

# core/protocol.py: one TDMA slot of Algorithm 1 and its three phases
PROTOCOL_SLOT = "protocol.slot"
SLOT_DECIDE = "decide"         # lines 14-24: worker decision, fade, wire
SLOT_SERVER = "server"         # lines 33-41: pricing, admission, G row
SLOT_OVERHEAR = "overhear"     # lines 26-31: independence test, R row
# core/protocol.py: the rest of a round
PROTOCOL_GRADS = "protocol.grads"        # worker gradients + attack plan
PROTOCOL_AGGREGATE = "protocol.aggregate"  # known-bad pad + fused CGC
PROTOCOL_UPDATE = "protocol.update"      # w - eta * g

# launch/engine.py: the Trainer's step (worker_fn)
TRAIN_GRADS = "train.grads"
TRAIN_AGGREGATE = "train.aggregate"
TRAIN_OPTIMIZER = "train.optimizer"

# models/: the layers the MoE + MLA configurations force
MOE_ROUTE = "moe.route"        # router, top-k, aux, sort, gather, combine
MOE_EXPERTS = "moe.experts"    # the held experts' grouped products
MOE_SHARED = "moe.shared"      # the shared experts
MLA_ATTENTION = "mla.attention"  # MLA from the projections through wo
