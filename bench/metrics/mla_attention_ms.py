"""Device ms per Trainer round in multi-head latent attention (scope
``mla.attention``: the projections, the latent norm, YaRN RoPE, the
chunked attention and wo), from the device trace (bench/spans.py).
"""
from pathlib import Path

from bench import spans

TRACE = Path(__file__).resolve().parents[1] / ".trace"


def read(ctx):
    try:
        from repro.obs.scopes import MLA_ATTENTION
    except ImportError:          # a program without these scopes
        return None
    return spans.scope_ms_per_round(ctx, MLA_ATTENTION, TRACE)
