from __future__ import annotations

from repro.kernels.backend import interpret_mode
from repro.kernels.rmsnorm.kernel import rmsnorm_pallas


def rmsnorm(x, scale, *, eps: float = 1e-6, bm: int = 128):
    """Fused RMSNorm over the last dim of a (M, d) array."""
    return rmsnorm_pallas(x, scale, bm=bm, eps=eps,
                          interpret=interpret_mode())
