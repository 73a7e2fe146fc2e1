"""Dequantization-free quantized matmul (the RMPU's job) — reference path.

LightNobel's RMPU computes ``Q(x) @ W`` directly on integer inliers and applies
the per-token scale **once after accumulation**, then adds the outlier partial
sums (which live in 16-bit fixed point and need no scale):

    y[t, :] = sigma[t] * (q[t, :] @ W) + sum_j ovals[t, j] * W[oidx[t, j], :]

The outlier term is a rank-k correction (k <= 4): each token's k outliers are
placed in an otherwise-zero (..., H) row by iota compare and contracted with
W in a second dot — a per-token gather of weight rows would move a (T, k, D)
copy of W through HBM on TPU.  The Pallas kernel in
``repro.kernels.aaq_matmul`` fuses all of it; this module is the oracle and
the always-works fallback.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.qtensor import QTensor, unpack_int4
from repro.core.quantize import place_outliers, quantize


def qmatmul(qt: QTensor, w: jax.Array, out_dtype=None) -> jax.Array:
    """y = dequant(qt) @ w, computed without materializing dequant(qt)."""
    assert w.shape[0] == qt.feature_dim, (w.shape, qt.feature_dim)
    out_dtype = out_dtype or qt.orig_dtype
    q = unpack_int4(qt.inliers) if qt.bits == 4 else qt.inliers
    q = q[..., :qt.feature_dim]
    wf = w.astype(jnp.float32)
    dot = lambda a: jax.lax.dot_general(
        a, wf, dimension_numbers=(((a.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # Integer contraction with f32 accumulation (MXU int8 path on real TPU).
    y = dot(q) * qt.scales                                  # scale once, at the end
    if qt.k_outliers:
        y = y + dot(place_outliers(jnp.zeros(q.shape, jnp.float32),
                                   qt.outlier_values, qt.outlier_idx))
    return y.astype(out_dtype)


def qmatmul_fused_ref(x: jax.Array, w: jax.Array, bits: int, k_outliers: int,
                      out_dtype=None) -> jax.Array:
    """quantize(x) then qmatmul — the end-to-end op models call."""
    return qmatmul(quantize(x, bits, k_outliers), w, out_dtype or x.dtype)
