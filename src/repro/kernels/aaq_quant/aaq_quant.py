"""Pallas TPU kernel: fused token-wise AAQ runtime quantization.

This is the ASIC VVPU's job mapped to the TPU VPU: one pass over a token
block in VMEM does top-k outlier extraction, scale computation, rounding and
INT4 nibble-packing — the activation never returns to HBM in high precision.

Tiling: grid over token blocks of ``block_t`` tokens; the feature dim H
(Hz = 128 in PPM — exactly one lane tile) stays whole inside the block, so
each token's reduction (top-k, max) is a purely in-register affair.

Everything in the body lowers with Mosaic: top-k (k <= 4) is
``core.quantize.top_k_abs`` (k rounds of max-|x|, first-index argmax by
iota compare, and mask); the INT4 pack is a small exact matmul against a
(H, H/2) placement matrix rather than a strided lane slice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.qtensor import qmax
from repro.core.quantize import top_k_abs

EPS = 1e-12


def _pack_int4(q):
    """(BT, H) int values in [-8, 7] -> (BT, H/2) int8, low nibble = even
    column (the ``core.qtensor.pack_int4`` layout)."""
    h = q.shape[-1]
    nib = (q.astype(jnp.int32) & 0x0F).astype(jnp.float32)   # 0..15
    r = jax.lax.broadcasted_iota(jnp.int32, (h, h // 2), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (h, h // 2), 1)
    place = (jnp.where(r == 2 * c, 1.0, 0.0)
             + jnp.where(r == 2 * c + 1, 16.0, 0.0))
    # every product and sum is an integer <= 255: exact at any MXU precision
    u = jax.lax.dot(nib, place, precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32).astype(jnp.int32)
    return jnp.where(u >= 128, u - 256, u).astype(jnp.int8)


def _quant_kernel(x_ref, inl_ref, scale_ref, ovals_ref, oidx_ref, *,
                  bits: int, k: int):
    x = x_ref[...].astype(jnp.float32)                       # (BT, H)
    if k > 0:
        ovals, oidx = top_k_abs(x, k)
        col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        hit = jnp.zeros(x.shape, jnp.bool_)
        for j in range(k):
            hit |= col == oidx[:, j:j + 1]
        inl = jnp.where(hit, 0.0, x)
        ovals_ref[...] = ovals.astype(jnp.bfloat16)
        oidx_ref[...] = oidx
    else:
        inl = x
        ovals_ref[...] = jnp.zeros(ovals_ref.shape, jnp.bfloat16)
        oidx_ref[...] = jnp.zeros(oidx_ref.shape, jnp.int32)
    m = jnp.max(jnp.abs(inl), axis=-1, keepdims=True)
    scale = jnp.maximum(m / qmax(bits), EPS)
    q = jnp.clip(jnp.round(inl / scale), -qmax(bits), qmax(bits))
    inl_ref[...] = _pack_int4(q) if bits == 4 else q.astype(jnp.int8)
    scale_ref[...] = scale


@functools.partial(jax.jit,
                   static_argnames=("bits", "k_outliers", "block_t", "interpret"))
def aaq_quantize_pallas(x: jax.Array, *, bits: int, k_outliers: int,
                        block_t: int = 256, interpret: bool):
    """x (T, H) -> (inliers, scales, ovals, oidx); T % block_t == 0 padding
    is handled here so callers can pass any T."""
    t, h = x.shape
    bt = min(block_t, t)
    pad = (-t) % bt
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    tp = x.shape[0]
    grid = (tp // bt,)
    h_out = h // 2 if bits == 4 else h
    kk = max(k_outliers, 1)
    kernel = functools.partial(_quant_kernel, bits=bits, k=k_outliers)
    out_shape = [
        jax.ShapeDtypeStruct((tp, h_out), jnp.int8),
        jax.ShapeDtypeStruct((tp, 1), jnp.float32),
        jax.ShapeDtypeStruct((tp, kk), jnp.bfloat16),
        jax.ShapeDtypeStruct((tp, kk), jnp.int32),
    ]
    out_specs = [
        pl.BlockSpec((bt, h_out), lambda i: (i, 0)),
        pl.BlockSpec((bt, 1), lambda i: (i, 0)),
        pl.BlockSpec((bt, kk), lambda i: (i, 0)),
        pl.BlockSpec((bt, kk), lambda i: (i, 0)),
    ]
    inl, scales, ovals, oidx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bt, h), lambda i: (i, 0))],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x)
    inl, scales = inl[:t], scales[:t]
    ovals, oidx = ovals[:t, :k_outliers], oidx[:t, :k_outliers]
    return inl, scales, ovals, oidx
