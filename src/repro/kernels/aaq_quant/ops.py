"""Public op: AAQ runtime quantization (kernel-backed, QTensor-returning)."""
from __future__ import annotations

import jax

from repro.core.qtensor import QTensor
from repro.kernels.aaq_quant.aaq_quant import aaq_quantize_pallas
from repro.kernels.aaq_quant.ref import aaq_quantize_ref


def aaq_quantize(x: jax.Array, bits: int, k_outliers: int, *,
                 block_t: int = 256, use_kernel: bool = True,
                 interpret: bool) -> QTensor:
    """Quantize an activation of any rank; token axis = -1."""
    shape = x.shape
    flat = x.reshape(-1, shape[-1])
    if use_kernel:
        inl, scales, ovals, oidx = aaq_quantize_pallas(
            flat, bits=bits, k_outliers=k_outliers, block_t=block_t,
            interpret=interpret)
    else:
        inl, scales, ovals, oidx = aaq_quantize_ref(flat, bits, k_outliers)
    lead = shape[:-1]
    return QTensor(
        inliers=inl.reshape(*lead, -1),
        scales=scales.reshape(*lead, 1),
        outlier_values=ovals.reshape(*lead, k_outliers),
        outlier_idx=oidx.reshape(*lead, k_outliers),
        bits=bits, k_outliers=k_outliers, feature_dim=shape[-1],
        orig_dtype=x.dtype)
