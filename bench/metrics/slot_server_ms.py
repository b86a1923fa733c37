"""Device ms per protocol round in the server phase of the slots (lines
33-41 of Algorithm 1): bit pricing, admission, the echo reconstruction
and the G row write (scope ``protocol.slot/server``), from the device
trace (bench/spans.py).
"""
from pathlib import Path

from bench import spans

TRACE = Path(__file__).resolve().parents[1] / ".trace"


def read(ctx):
    try:
        from repro.obs.scopes import PROTOCOL_SLOT, SLOT_SERVER
    except ImportError:          # a program without these scopes
        return None
    return spans.scope_ms_per_round(ctx, f"{PROTOCOL_SLOT}/{SLOT_SERVER}",
                                    TRACE)
