"""Faults planted in the program, to show that the comparison catches them.

Each driver lists the faults a cell of its kind can have in its own
``FAULTS`` (name -> context manager). A fault breaks the timed path
underneath the harness, inside the program's own modules; it is active
while the cell is built and driven, and undone after. The names used:

  unchanged   a step returns its state unchanged
  half_batch  half of the batch (of the workers) left out, the mean
              (the sum, scaled) taken over the rest
  altered     an answer altered where it is produced
"""
from __future__ import annotations

import contextlib

import jax


@contextlib.contextmanager
def patched(module, name, make):
    """``module.name`` replaced by ``make(original)`` while active."""
    orig = getattr(module, name)
    setattr(module, name, make(orig))
    jax.clear_caches()       # no program traced before the fault is reused
    try:
        yield
    finally:
        setattr(module, name, orig)
        jax.clear_caches()
