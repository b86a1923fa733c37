"""Device ms per protocol round in the echo projection of every slot:
the masked Gram, A^T g, the solve and the echo (scope ``echo.project``),
from the ops' scope paths in the device trace (bench/spans.py).
"""
from pathlib import Path

from bench import spans

TRACE = Path(__file__).resolve().parents[1] / ".trace"


def read(ctx):
    try:
        from repro.obs.scopes import ECHO_PROJECT
    except ImportError:          # a program without these scopes
        return None
    return spans.scope_ms_per_round(ctx, ECHO_PROJECT, TRACE)
