"""Where the Pallas kernels run: compiled by Mosaic on a TPU, interpreted on
the CPU (the test backend). No other backend has a path."""
from __future__ import annotations

from typing import Optional

import jax

__all__ = ["resolve_interpret"]


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Resolve a kernel wrapper's ``interpret=None`` from the default backend.

    TPU → compiled kernel; CPU → interpret mode. Any other backend raises:
    interpreting a TPU kernel there would hide the device from the caller.
    An explicit bool is returned unchanged (ahead-of-time compiles for a
    described TPU pass ``False`` from a CPU process).
    """
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels target the TPU; backend {backend!r} has no "
        f"compiled path, and interpret mode is kept for the CPU. Serve "
        f"with use_kernel=False (the jnp path) on this backend."
    )
