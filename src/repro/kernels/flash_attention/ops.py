from __future__ import annotations

from repro.kernels.backend import interpret_mode
from repro.kernels.flash_attention.kernel import flash_attention_pallas


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128):
    """Flash attention over (BH, S, d) tensors (heads pre-flattened)."""
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, bq=bq, bk=bk,
        interpret=interpret_mode())
