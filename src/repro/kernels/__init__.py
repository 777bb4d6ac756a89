"""Pallas TPU kernels (``ops`` wraps them; ``ref`` holds the oracles)."""
from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Whether a Pallas kernel runs in interpret mode.

    ``None`` decides from the backend: compiled on a TPU, interpreted
    anywhere else (the CPU executes the kernel body).  Interpreting on a
    TPU is refused: it would run the kernel as slow host-sequenced XLA ops
    and hide what the chip does with it.
    """
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas kernels are compiled on a TPU, never "
                         "interpreted")
    return interpret
