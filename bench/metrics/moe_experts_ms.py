"""Device ms per Trainer round in the held experts' grouped products
(scope ``moe.experts``: the gate, up and down products of the held copies
and the SwiGLU between them, forward, rematerialised and backward), from
the device trace (bench/spans.py). XLA lowers each ``ragged_dot`` to TPU
kernels named ``ragged-dot-*`` whose op metadata keeps no scope, so those
are found by name; this program has no other ragged product.
"""
from pathlib import Path

from bench import spans
from bench.trace import short

TRACE = Path(__file__).resolve().parents[1] / ".trace"
KERNEL = "ragged-dot"


def read(ctx):
    try:
        from repro.obs.scopes import MOE_EXPERTS
    except ImportError:          # a program without these scopes
        return None
    rounds = ctx["counters"].get("rounds")
    sp = spans.of_run(ctx, TRACE)
    if sp is None or not rounds:
        return None
    seen = {}

    def mine(name, tf_op):
        if short(name).startswith(KERNEL):
            return True
        if tf_op not in seen:
            seen[tf_op] = spans.in_scope(tf_op, MOE_EXPERTS)
        return seen[tf_op]

    if not any(mine(n, t) for evs in sp.ops.values() for n, _, _, t in evs):
        return None
    per_chip = [sum(e - s for n, s, e, t in sp.leaves(c) if mine(n, t))
                for c in sp.ops]
    return 1e-6 * sum(per_chip) / len(per_chip) / rounds
