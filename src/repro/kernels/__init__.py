"""Pallas TPU kernels (``quantize``, ``preprocess``, ``flash_attention``),
their jitted wrappers (``ops``) and pure-jnp oracles (``ref``)."""
from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> interpret only on the CPU backend, where no chip can run
    the kernel; on a TPU every kernel compiles.  An explicit bool wins (the
    compile tests lower for a described TPU from a CPU process)."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret
