"""Public op: fused AAQ linear  y = dequant-free-matmul(quantize(x), W).

Composes the two kernels; this is the op the optimized PPM dataflow calls in
place of ``scheme.linear`` (see models/ppm and EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import math

import jax

from repro.kernels.aaq_matmul.aaq_matmul import aaq_matmul_pallas
from repro.kernels.aaq_matmul.ref import aaq_matmul_ref
from repro.kernels.aaq_quant.ops import aaq_quantize


def aaq_linear(x: jax.Array, w: jax.Array, *, bits: int, k_outliers: int,
               block_t: int = 256, block_d: int = 256,
               use_kernel: bool = True, interpret: bool) -> jax.Array:
    """x (..., H) @ w (H, D) through the packed AAQ path."""
    lead = x.shape[:-1]
    qt = aaq_quantize(x, bits, k_outliers, block_t=block_t,
                      use_kernel=use_kernel, interpret=interpret)
    nt = math.prod(lead) if lead else 1
    flat = lambda a: a.reshape(nt, a.shape[-1])
    if use_kernel:
        y = aaq_matmul_pallas(flat(qt.inliers), flat(qt.scales),
                              flat(qt.outlier_values), flat(qt.outlier_idx),
                              w, bits=bits, block_t=block_t, block_d=block_d,
                              out_dtype=x.dtype, interpret=interpret)
    else:
        y = aaq_matmul_ref(flat(qt.inliers), flat(qt.scales),
                           flat(qt.outlier_values), flat(qt.outlier_idx),
                           w, bits=bits, out_dtype=x.dtype)
    return y.reshape(*lead, w.shape[-1])
