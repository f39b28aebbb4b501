"""Where the Pallas kernels run: compiled on a TPU, interpreted on the CPU."""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """``interpret=`` for a ``pallas_call`` on the default backend.

    The kernels are written for the TPU, where they compile. The CPU runs
    them in Pallas's interpreter, so the tests can check them there. Any
    other backend is refused rather than silently interpreted, so no
    accelerator run ever measures the interpreter."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels compile for the TPU and are interpreted on the "
        f"CPU; backend {backend!r} is neither")
