"""Pallas TPU kernels for the cascade's compute hot spots.

Each kernel package ships:
* ``kernel.py`` — pl.pallas_call with explicit BlockSpec VMEM tiling
* ``ops.py``    — jit'd public wrapper (compiled on TPU, interpreted on CPU;
  see ``backend.interpret_mode``)
* ``ref.py``    — pure-jnp oracle used by the allclose test sweeps
"""
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.ssd_chunk.ops import ssd_chunk
from repro.kernels.zoo_dual_matmul.ops import zoo_dual_matmul

__all__ = ["flash_attention", "rmsnorm", "ssd_chunk", "zoo_dual_matmul"]
