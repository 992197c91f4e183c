"""Pallas TPU kernels of the protected datapath.

Every kernel takes ``interpret=``: ``None`` (the default everywhere) follows
the platform — interpreted on the CPU backend, compiled for the chip on
every other.  Tests that compile for a described chip pass ``False``.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret`` as given, or — for ``None`` — whether the default
    backend is the CPU (the only place a Pallas TPU kernel is interpreted)."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret
