"""Device ms per Trainer round in the optimizer update and its apply to
the weights (scope ``train.optimizer``), from the device trace
(bench/spans.py).
"""
from pathlib import Path

from bench import spans

TRACE = Path(__file__).resolve().parents[1] / ".trace"


def read(ctx):
    try:
        from repro.obs.scopes import TRAIN_OPTIMIZER
    except ImportError:          # a program without these scopes
        return None
    return spans.scope_ms_per_round(ctx, TRAIN_OPTIMIZER, TRACE)
