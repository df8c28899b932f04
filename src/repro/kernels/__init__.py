"""Pallas kernels for the compute hot spots (FedDPC server epilogue,
flash attention, selective scan). Each ``<name>/ops.py`` entry point
takes ``interpret=None`` and resolves it with ``resolve_interpret``: the
compiled Mosaic kernel on a TPU, the Pallas interpreter elsewhere."""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret`` as given, or — when None — interpret mode exactly
    when the default backend is not a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
