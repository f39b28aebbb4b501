"""Public wrapper: compiled Pallas on the TPU, interpreted on the CPU."""
from __future__ import annotations

from repro.kernels.backend import interpret_mode
from repro.kernels.zoo_dual_matmul.kernel import (
    zoo_dual_matmul_pallas, zoo_dual_matmul_stacked_bias_relu_pallas,
    zoo_dual_matmul_stacked_pallas)


def zoo_dual_matmul(x, w, u, mu, *, bm: int = 128, bn: int = 128):
    """y = x @ w ; y_hat = x @ (w + mu*u) — one fused pass."""
    return zoo_dual_matmul_pallas(x, w, u, mu, bm=bm, bn=bn,
                                  interpret=interpret_mode())


def zoo_dual_matmul_stacked(x, w, us, mu, *, b=None, ub=None,
                            bm: int = 128, bn: int = 128):
    """y = x @ w ; y_hat[l] = x @ (w + mu*us[l]) for all q lanes — the xW
    product is computed once and shared across lanes.

    Passing ``b`` (N,) and ``ub`` (q, N) fuses the tabular client's
    bias+ReLU epilogue into the same pass: returns
    (relu(xW + b), relu(x(W + μU_l) + b + μu_b_l)) with the activation
    applied on tiles still resident in VMEM."""
    if (b is None) != (ub is None):
        raise ValueError("pass both b and ub for the fused epilogue, "
                         "or neither")
    if b is not None:
        return zoo_dual_matmul_stacked_bias_relu_pallas(
            x, w, us, b, ub, mu, bm=bm, bn=bn, interpret=interpret_mode())
    return zoo_dual_matmul_stacked_pallas(x, w, us, mu, bm=bm, bn=bn,
                                          interpret=interpret_mode())
