"""Device ms per Trainer round in the MoE routing (scope ``moe.route``:
the router, top-k, aux loss, the sort of the copies by held expert, their
gather and the weighted combine), from the device trace (bench/spans.py).
"""
from pathlib import Path

from bench import spans

TRACE = Path(__file__).resolve().parents[1] / ".trace"


def read(ctx):
    try:
        from repro.obs.scopes import MOE_ROUTE
    except ImportError:          # a program without these scopes
        return None
    return spans.scope_ms_per_round(ctx, MOE_ROUTE, TRACE)
