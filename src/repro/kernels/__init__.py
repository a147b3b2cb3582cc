"""Pallas kernels of the index plane (and the dormant LM plane).

Every kernel takes ``interpret=None`` by default, resolved by
:func:`use_interpret`: compiled on a TPU backend, the Pallas interpreter
anywhere else.  Oracles live in ``ref.py`` with identical signatures."""

import jax


def use_interpret() -> bool:
    """Kernels execute their Python bodies (interpret mode) unless a real
    TPU backend is present."""
    return jax.default_backend() != "tpu"
