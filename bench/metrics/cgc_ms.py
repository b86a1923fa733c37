"""Device ms per protocol round in the aggregation: the known-bad pad of
the table and the fused CGC kernel (scope ``protocol.aggregate``), from
the device trace (bench/spans.py).
"""
from pathlib import Path

from bench import spans

TRACE = Path(__file__).resolve().parents[1] / ".trace"


def read(ctx):
    try:
        from repro.obs.scopes import PROTOCOL_AGGREGATE
    except ImportError:          # a program without these scopes
        return None
    return spans.scope_ms_per_round(ctx, PROTOCOL_AGGREGATE, TRACE)
