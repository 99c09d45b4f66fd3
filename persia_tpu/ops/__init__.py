"""Pallas kernels of the towers, and the plain forms beside them."""

import jax


def interpret(choice="auto") -> bool:
    """Whether a Pallas kernel runs interpreted: ``"auto"`` compiles it on
    a TPU and interprets it on any other backend; ``True`` or ``False`` is
    taken as given (``False`` lowers for a TPU from a machine without
    one)."""
    if choice == "auto":
        return jax.default_backend() != "tpu"
    return choice
