"""Fused clean+perturbed client forward: y = xW and ŷ = x(W+μU) in ONE pass.

The cascade's client computes both c = F_m(w) and ĉ = F_m(w+μu) every round
(paper Alg. 1 line 4). Done naively that is two full forwards — 2× HBM
traffic on x and W(+U). This kernel reads each x/W/U tile into VMEM once
and emits both outputs: for the memory-bound embedding/projection client
models this halves the bytes moved (x read once, and ŷ's extra work is one
fused multiply-add on tiles already resident in VMEM).

Tiling: grid over (M/bm, N/bn); each program reads the full-K stripes
x (bm, K), W/U (K, bn), and bm/bn are 128-multiples for the MXU. The
pipeline double-buffers every block, so the working set is about
2·(bm·K + 2·K·bn + 2·bm·bn) elements: at K = 7168 in f32 that is ~22 MB,
above the 16 MB of VMEM a TPU v5e program may use by default. Each call
therefore asks the compiler for the scoped VMEM its blocks need
(:func:`_vmem_limit`). The scalar μ lives in SMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the scoped-VMEM limit a TPU v5e program gets unless it asks for more
_DEFAULT_SCOPED_VMEM = 16 * 2 ** 20
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _vmem_limit(dtype, *blocks) -> pltpu.CompilerParams:
    """Compiler params whose scoped-VMEM limit covers the pipelined
    ``blocks`` (shapes in elements of ``dtype``), each double-buffered,
    plus the (bm, bn) f32 accumulator and half again as headroom."""
    size = jnp.dtype(dtype).itemsize
    need = sum(2 * size * math.prod(b) for b in blocks)
    need += 4 * math.prod(blocks[-1])
    return pltpu.CompilerParams(
        vmem_limit_bytes=max(_DEFAULT_SCOPED_VMEM, need * 3 // 2))


def _dual_matmul_kernel(x_ref, w_ref, u_ref, mu_ref, y_ref, y_hat_ref):
    x = x_ref[...]
    w = w_ref[...]
    u = u_ref[...]
    mu = mu_ref[0]
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    # ŷ = xW + μ(xU): reuse the xW product already in registers
    yu = jnp.dot(x, u, preferred_element_type=jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    y_hat_ref[...] = (y + mu * yu).astype(y_hat_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def zoo_dual_matmul_pallas(x, w, u, mu, *, bm: int = 128, bn: int = 128,
                           interpret: bool = False):
    """x (M, K), w/u (K, N), mu scalar -> (y (M, N), y_hat (M, N))."""
    M, K = x.shape
    _, N = w.shape
    bm = min(bm, M)
    bn = min(bn, N)
    assert M % bm == 0 and N % bn == 0, (M, N, bm, bn)
    mu_arr = jnp.asarray([mu], jnp.float32)

    grid = (M // bm, N // bn)
    return pl.pallas_call(
        _dual_matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, bn), lambda i, j: (0, j)),
            pl.BlockSpec((K, bn), lambda i, j: (0, j)),
            _SMEM,
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), x.dtype),
            jax.ShapeDtypeStruct((M, N), x.dtype),
        ],
        compiler_params=_vmem_limit(x.dtype, (bm, K), (K, bn), (K, bn),
                                    (bm, bn), (bm, bn)),
        interpret=interpret,
    )(x, w, u, mu_arr)


def _dual_matmul_stacked_kernel(x_ref, w_ref, u_ref, mu_ref,
                                y_ref, y_hat_ref, acc_ref):
    """Stacked ZOO fan-out: ŷ_l = xW + μ(xU_l) for all q lanes.

    Grid is (M/bm, N/bn, q) with the lane axis innermost, so for a fixed
    output tile the xW product is computed ONCE (lane 0), parked in a VMEM
    scratch accumulator, and re-used by every perturbation lane while the
    x/W tiles stay resident — HBM traffic on x and W is constant in q."""
    lane = pl.program_id(2)
    x = x_ref[...]

    @pl.when(lane == 0)
    def _():
        acc_ref[...] = jnp.dot(x, w_ref[...],
                               preferred_element_type=jnp.float32)
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)

    yu = jnp.dot(x, u_ref[0], preferred_element_type=jnp.float32)
    y_hat_ref[0] = (acc_ref[...] + mu_ref[0] * yu).astype(y_hat_ref.dtype)


def _dual_matmul_stacked_bias_relu_kernel(x_ref, w_ref, u_ref, b_ref,
                                          ub_ref, mu_ref, y_ref, y_hat_ref,
                                          acc_ref):
    """Stacked fan-out with the tabular client's bias+ReLU epilogue fused.

    Same lane-innermost tiling as :func:`_dual_matmul_stacked_kernel`; the
    scratch accumulator parks the RAW xW product (bias-free, so every
    perturbation lane can re-derive its own pre-activation), and each
    lane's bias add + ReLU runs on the tile while it is still resident in
    VMEM — the activated outputs go straight to HBM, so the epilogue costs
    zero extra memory traffic vs the unfused matmul alone (the unfused
    path re-reads both outputs from HBM to add bias and clamp)."""
    lane = pl.program_id(2)
    x = x_ref[...]
    b = b_ref[0]

    @pl.when(lane == 0)
    def _():
        acc_ref[...] = jnp.dot(x, w_ref[...],
                               preferred_element_type=jnp.float32)
        y_ref[...] = jnp.maximum(acc_ref[...] + b, 0.0).astype(y_ref.dtype)

    yu = jnp.dot(x, u_ref[0], preferred_element_type=jnp.float32)
    mu = mu_ref[0]
    # lane l pre-activation: x(W + μU_l) + (b + μu_b_l)
    pre = acc_ref[...] + mu * yu + (b + mu * ub_ref[0, 0])
    y_hat_ref[0] = jnp.maximum(pre, 0.0).astype(y_hat_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def zoo_dual_matmul_stacked_bias_relu_pallas(x, w, us, b, ub, mu, *,
                                             bm: int = 128, bn: int = 128,
                                             interpret: bool = False):
    """x (M, K), w (K, N), us (q, K, N), b (N,), ub (q, N), mu scalar ->
    (y (M, N), y_hat (q, M, N)) with the epilogue fused:
    y = relu(xW + b), ŷ_l = relu(x(W + μU_l) + b + μu_b_l)."""
    M, K = x.shape
    _, N = w.shape
    q = us.shape[0]
    assert us.shape == (q, K, N), (us.shape, (q, K, N))
    assert b.shape == (N,) and ub.shape == (q, N), (b.shape, ub.shape)
    bm = min(bm, M)
    bn = min(bn, N)
    assert M % bm == 0 and N % bn == 0, (M, N, bm, bn)
    mu_arr = jnp.asarray([mu], jnp.float32)
    b2 = b.astype(jnp.float32)[None]                      # (1, N)
    # (q, 1, N): a lane's (1, bn) block then spans the whole second-minor
    # dim, which the TPU tiling accepts; a (1, bn) block of (q, N) it refuses
    ub2 = ub.astype(jnp.float32)[:, None]                 # (q, 1, N)

    grid = (M // bm, N // bn, q)
    return pl.pallas_call(
        _dual_matmul_stacked_bias_relu_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j, l: (i, 0)),
            pl.BlockSpec((K, bn), lambda i, j, l: (0, j)),
            pl.BlockSpec((1, K, bn), lambda i, j, l: (l, 0, j)),
            pl.BlockSpec((1, bn), lambda i, j, l: (0, j)),
            pl.BlockSpec((1, 1, bn), lambda i, j, l: (l, 0, j)),
            _SMEM,
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
            pl.BlockSpec((1, bm, bn), lambda i, j, l: (l, i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), x.dtype),
            jax.ShapeDtypeStruct((q, M, N), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=_vmem_limit(x.dtype, (bm, K), (K, bn), (K, bn),
                                    (bm, bn), (bm, bn)),
        interpret=interpret,
    )(x, w, us, b2, ub2, mu_arr)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def zoo_dual_matmul_stacked_pallas(x, w, us, mu, *, bm: int = 128,
                                   bn: int = 128, interpret: bool = False):
    """x (M, K), w (K, N), us (q, K, N), mu scalar ->
    (y (M, N), y_hat (q, M, N)) with ŷ_l = x(W + μU_l)."""
    M, K = x.shape
    _, N = w.shape
    q = us.shape[0]
    assert us.shape == (q, K, N), (us.shape, (q, K, N))
    bm = min(bm, M)
    bn = min(bn, N)
    assert M % bm == 0 and N % bn == 0, (M, N, bm, bn)
    mu_arr = jnp.asarray([mu], jnp.float32)

    grid = (M // bm, N // bn, q)
    return pl.pallas_call(
        _dual_matmul_stacked_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j, l: (i, 0)),
            pl.BlockSpec((K, bn), lambda i, j, l: (0, j)),
            pl.BlockSpec((1, K, bn), lambda i, j, l: (l, 0, j)),
            _SMEM,
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
            pl.BlockSpec((1, bm, bn), lambda i, j, l: (l, i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), x.dtype),
            jax.ShapeDtypeStruct((q, M, N), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=_vmem_limit(x.dtype, (bm, K), (K, bn), (K, bn),
                                    (bm, bn), (bm, bn)),
        interpret=interpret,
    )(x, w, us, mu_arr)
