"""Device ms per protocol round in the overhearing phase of the slots
(lines 26-31 of Algorithm 1): the independence test and the R and mask
writes (scope ``protocol.slot/overhear``), from the device trace
(bench/spans.py).
"""
from pathlib import Path

from bench import spans

TRACE = Path(__file__).resolve().parents[1] / ".trace"


def read(ctx):
    try:
        from repro.obs.scopes import PROTOCOL_SLOT, SLOT_OVERHEAR
    except ImportError:          # a program without these scopes
        return None
    return spans.scope_ms_per_round(ctx, f"{PROTOCOL_SLOT}/{SLOT_OVERHEAR}",
                                    TRACE)
