"""Faults planted under a cell's timed path, and the lower-precision
control, for the check to catch.

Each takes a ``setattr(obj, name, value)`` callable (pytest's
``monkeypatch.setattr``, or :class:`Patch`) and plants itself before the
trainer is built.
"""
from __future__ import annotations


def state_unchanged(setattr_):
    """Each round returns the state it was given (its loss still computed)."""
    from repro.core import GluADFL

    orig = GluADFL._round

    def frozen(self, state, *a, **k):
        _, aux = orig(self, state, *a, **k)
        return state, aux

    setattr_(GluADFL, "_round", frozen)


def half_batch(setattr_):
    """Each node's batch keeps half its rows; the mean is over those."""
    from repro.core import GluADFL

    orig = GluADFL._sample_batch
    setattr_(GluADFL, "_sample_batch",
             lambda self, key, x, y, c, bs: orig(self, key, x, y, c, bs // 2))


def gossip_left_out(setattr_):
    """No exchange between nodes: every node keeps its own parameters."""
    from repro.core import GluADFL

    setattr_(GluADFL, "_gossip", lambda self, premix, *a, **k: premix)


def control_bf16(setattr_):
    """The plain reference, computed in bfloat16, in the program's place."""
    import jax.numpy as jnp

    from bench.kinds import federation as fed

    orig = fed.first_chunk

    def lower(cell, trainer, data, state, keys):
        state, _ = orig(cell, trainer, data, state, keys)
        return state, fed.reference(cell, keys, data, cell.config["chunk"], dtype=jnp.bfloat16)

    setattr_(fed, "first_chunk", lower)


# per traffic kind: the faults its cells can have, and the control
FAULTS = {
    "federation": {
        "state_unchanged": state_unchanged,
        "half_batch": half_batch,
        "gossip_left_out": gossip_left_out,
        "control_bf16": control_bf16,
    },
}


class Patch:
    """``setattr`` that remembers the old values and puts them back."""

    def __init__(self):
        self._undo = []

    def __call__(self, obj, name, value):
        self._undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        for obj, name, value in reversed(self._undo):
            setattr(obj, name, value)
