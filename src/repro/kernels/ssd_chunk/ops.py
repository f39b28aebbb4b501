from __future__ import annotations

from repro.kernels.backend import interpret_mode
from repro.kernels.ssd_chunk.kernel import ssd_chunk_pallas


def ssd_chunk(xh, a, dt, bm, cm, *, chunk: int = 128):
    """Mamba2 SSD over (BH, S, ·) tensors (batch·heads pre-flattened;
    B/C broadcast over heads by the caller)."""
    return ssd_chunk_pallas(xh, a, dt, bm, cm, chunk=chunk,
                            interpret=interpret_mode())
