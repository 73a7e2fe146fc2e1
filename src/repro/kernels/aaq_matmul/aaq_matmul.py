"""Pallas TPU kernel: dequantization-free quantized matmul (the RMPU).

y[t, :] = sigma[t] * (q[t, :] @ W)  +  sum_j ovals[t, j] * W[oidx[t, j], :]

Design notes (TPU adaptation of the RMPU, see DESIGN.md §2):
  * INT4 inliers arrive nibble-packed (half the HBM traffic of INT8).  The
    low nibbles are the even feature columns and the high nibbles the odd
    ones, so the kernel sign-extends each half in VMEM and runs two
    half-width dots against the even and odd weight rows (split once, in
    the wrapper) — no lane interleave is ever built.
  * The per-token scale multiplies the *accumulated* row once — LightNobel's
    deferred dequantization. No f32 copy of the activation ever exists in HBM.
  * Outliers are a rank-k correction (k <= 4): the k (index, value) pairs of
    each token are scattered into a sparse (BT, H) row by iota compare and
    contracted against the same weight block, instead of a row gather.
  * Grid: (T/block_t, D/block_d); the contraction dim H (= 128 in PPM) stays
    whole per block — MXU-aligned and small enough that no H-tiling is needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _outlier_rows(ovals, oidx, width: int, stride: int, offset: int):
    """(BT, width) f32 rows holding ovals[t, j] at column c where
    oidx[t, j] == stride * c + offset, zero elsewhere."""
    col = stride * jax.lax.broadcasted_iota(
        jnp.int32, (oidx.shape[0], width), 1) + offset
    rows = jnp.zeros(col.shape, jnp.float32)
    for j in range(oidx.shape[1]):
        rows = rows + jnp.where(col == oidx[:, j:j + 1], ovals[:, j:j + 1],
                                0.0)
    return rows


def _qmm_kernel(inl_ref, scale_ref, ovals_ref, oidx_ref, *refs,
                bits: int, k: int, out_dtype):
    *w_refs, o_ref = refs                                    # 1 or 2 weights
    dot = functools.partial(jax.lax.dot, preferred_element_type=jnp.float32)
    q = inl_ref[...].astype(jnp.int32)                       # (BT, H or H/2)
    ws = [w[...].astype(jnp.float32) for w in w_refs]
    if bits == 4:
        lo = (q << 28) >> 28                                 # even columns
        hi = q >> 4                                          # odd columns
        w_even, w_odd = ws
        acc = (dot(lo.astype(jnp.float32), w_even)
               + dot(hi.astype(jnp.float32), w_odd))
    else:
        acc = dot(q.astype(jnp.float32), ws[0])              # (BT, BD)
    y = acc * scale_ref[...]                                 # deferred scale
    if k > 0:
        oidx = oidx_ref[...]                                 # (BT, K)
        ovals = ovals_ref[...].astype(jnp.float32)
        if bits == 4:
            half = q.shape[1]
            y = y + dot(_outlier_rows(ovals, oidx, half, 2, 0), w_even)
            y = y + dot(_outlier_rows(ovals, oidx, half, 2, 1), w_odd)
        else:
            y = y + dot(_outlier_rows(ovals, oidx, q.shape[1], 1, 0), ws[0])
    o_ref[...] = y.astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("bits", "block_t", "block_d",
                                             "out_dtype", "interpret"))
def aaq_matmul_pallas(inliers, scales, ovals, oidx, w, *, bits: int,
                      block_t: int = 256, block_d: int = 256,
                      out_dtype=jnp.float32, interpret: bool):
    t = inliers.shape[0]
    h, d = w.shape
    k = ovals.shape[-1]
    bt, bd = min(block_t, t), min(block_d, d)
    pad_t, pad_d = (-t) % bt, (-d) % bd
    if pad_t:
        inliers = jnp.pad(inliers, ((0, pad_t), (0, 0)))
        scales = jnp.pad(scales, ((0, pad_t), (0, 0)))
        ovals = jnp.pad(ovals, ((0, pad_t), (0, 0)))
        oidx = jnp.pad(oidx, ((0, pad_t), (0, 0)))
    if pad_d:
        w = jnp.pad(w, ((0, 0), (0, pad_d)))
    tp, dp = inliers.shape[0], w.shape[1]
    hp = inliers.shape[1]                                    # H or H/2
    kk = max(k, 1)
    if k == 0:  # keep kernel arity fixed; dummy zero-width-safe operands
        ovals = jnp.zeros((tp, 1), jnp.bfloat16)
        oidx = jnp.zeros((tp, 1), jnp.int32)
    # even / odd weight rows; lax slices, since a ``w[0::2]`` index lowers to
    # a gather on TPU
    ws = ((jax.lax.slice_in_dim(w, 0, h, stride=2),
           jax.lax.slice_in_dim(w, 1, h, stride=2)) if bits == 4 else (w,))
    kernel = functools.partial(_qmm_kernel, bits=bits, k=k,
                               out_dtype=jnp.dtype(out_dtype))
    y = pl.pallas_call(
        kernel,
        grid=(tp // bt, dp // bd),
        in_specs=[
            pl.BlockSpec((bt, hp), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, kk), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, kk), lambda i, j: (i, 0)),
        ] + [pl.BlockSpec((wp.shape[0], bd), lambda i, j: (0, j))
             for wp in ws],
        out_specs=pl.BlockSpec((bt, bd), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((tp, dp), jnp.dtype(out_dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(inliers, scales, ovals, oidx, *ws)
    return y[:t, :d]
