"""Token-wise Adaptive Activation Quantization (AAQ) — reference path.

Pure-jnp implementation of the paper's §4.1 runtime quantization (the ASIC
VVPU's job).  The Pallas kernel in ``repro.kernels.aaq_quant`` is the fused
drop-in; this module is the semantic definition and the oracle.
"""
from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.qtensor import QTensor, pack_int4, qmax, unpack_int4

_EPS = 1e-12


def _slot_hits(idx: jax.Array, h: int) -> list[jax.Array]:
    """One (..., h) bool mask per outlier slot: True at column idx[..., j].

    Compares against an iota instead of gathering/scattering by index: on
    TPU, XLA lowers per-token scatters over every token of a pair tensor to
    hundreds of GB of memory traffic; these selects move each value once.
    """
    col = jax.lax.broadcasted_iota(jnp.int32, (*idx.shape[:-1], h),
                                   idx.ndim - 1)
    return [col == idx[..., j:j + 1] for j in range(idx.shape[-1])]


def top_k_abs(x: jax.Array, k: int):
    """(values, indices), (..., k) each: the entries of the k largest |x|
    along the last axis, largest first — ``lax.top_k(|x|, k)``'s choice,
    ties broken toward the lower index.

    k rounds of max-|x|, first-index argmax by iota compare, and mask.  For
    the small k of the AAQ groups this beats ``lax.top_k``, which XLA lowers
    on TPU to a full sort of every token, and it lowers in Mosaic too (the
    fused quantization kernel calls it on its (BT, H) block).
    """
    h, ax = x.shape[-1], x.ndim - 1
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, ax)
    slot = jax.lax.broadcasted_iota(jnp.int32, (*x.shape[:-1], k), ax)
    a = jnp.abs(x)
    vals = jnp.zeros(slot.shape, x.dtype)
    idx = jnp.zeros(slot.shape, jnp.int32)
    for j in range(k):
        m = jnp.max(a, axis=-1, keepdims=True)
        at = jnp.min(jnp.where(a == m, col, h), axis=-1, keepdims=True)
        hit = col == at
        v = jnp.sum(jnp.where(hit, x, 0), axis=-1, keepdims=True)
        vals = jnp.where(slot == j, v, vals)
        idx = jnp.where(slot == j, at, idx)
        a = jnp.where(hit, -1, a)                            # |x| >= 0
    return vals, idx


def _split_outliers(x: jax.Array, k: int):
    """Dynamic top-k outlier split (paper: VVPU bitonic top-k, k static/group).

    Returns (inlier_x, outlier_values, outlier_idx) with outlier slots zeroed
    in ``inlier_x`` so the integer matmul path never double-counts them.
    """
    if k == 0:
        zshape = (*x.shape[:-1], 0)
        return (x, jnp.zeros(zshape, jnp.bfloat16),
                jnp.zeros(zshape, jnp.int32))
    vals, idx = top_k_abs(x, k)
    mask = functools.reduce(jnp.logical_or, _slot_hits(idx, x.shape[-1]))
    return jnp.where(mask, 0.0, x), vals.astype(jnp.bfloat16), idx


def _quantize_inliers(inl: jax.Array, bits: int):
    """(integer-valued f32 q in [-qmax, qmax], per-token sigma)."""
    m = jnp.max(jnp.abs(inl), axis=-1, keepdims=True)
    sigma = jnp.maximum(m / qmax(bits), _EPS)
    return jnp.clip(jnp.round(inl / sigma), -qmax(bits), qmax(bits)), sigma


def place_outliers(x: jax.Array, vals: jax.Array, idx: jax.Array):
    """``x`` with each token's outlier values written back at their columns."""
    vals = vals.astype(jnp.float32)
    for j, hit in enumerate(_slot_hits(idx, x.shape[-1])):
        x = jnp.where(hit, vals[..., j:j + 1], x)
    return x


def quantize(x: jax.Array, bits: int, k_outliers: int) -> QTensor:
    """Uniform symmetric token-wise quantization with top-k outlier handling.

    Eq. (1):  M = max(|min|, |max|) over inliers;  sigma = M / (2^(m-1)-1);
    Q(x) = round(x / sigma).
    """
    assert bits in (4, 8), bits
    h = x.shape[-1]
    inl, ovals, oidx = _split_outliers(x.astype(jnp.float32), k_outliers)
    q, sigma = _quantize_inliers(inl, bits)
    q = q.astype(jnp.int8)
    if bits == 4:
        if q.shape[-1] % 2:                       # odd feature dim: pad a lane
            q = jnp.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, 1)])
        q = pack_int4(q)
    return QTensor(inliers=q, scales=sigma, outlier_values=ovals,
                   outlier_idx=oidx, bits=bits, k_outliers=k_outliers,
                   feature_dim=h, orig_dtype=x.dtype)


def dequantize(qt: QTensor) -> jax.Array:
    """Reconstruct x_hat: scaled inliers + outliers written back in place."""
    q = unpack_int4(qt.inliers) if qt.bits == 4 else qt.inliers
    q = q[..., :qt.feature_dim]                   # drop int4 pad lane if any
    x = q.astype(jnp.float32) * qt.scales
    if qt.k_outliers:
        x = place_outliers(x, qt.outlier_values, qt.outlier_idx)
    return x.astype(qt.orig_dtype)


def fake_quant(x: jax.Array, bits: int, k_outliers: int) -> jax.Array:
    """``dequantize(quantize(x))``, value for value, without building the
    QTensor: the INT4 nibble pack and unpack are the identity on the
    rounded inliers, and on TPU they cost a relayout of every token."""
    assert bits in (4, 8), bits
    inl, ovals, oidx = _split_outliers(x.astype(jnp.float32), k_outliers)
    q, sigma = _quantize_inliers(inl, bits)
    xh = q * sigma
    if k_outliers:
        xh = place_outliers(xh, ovals, oidx)
    return xh.astype(x.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def fake_quant_ste(x: jax.Array, bits: int, k_outliers: int) -> jax.Array:
    return fake_quant(x, bits, k_outliers)


def _fq_fwd(x, bits, k_outliers):
    return fake_quant(x, bits, k_outliers), None


def _fq_bwd(bits, k_outliers, _, g):
    return (g,)  # straight-through estimator


fake_quant_ste.defvjp(_fq_fwd, _fq_bwd)


def quant_rmse(x: jax.Array, bits: int, k_outliers: int) -> jax.Array:
    """RMSE of the quantization round trip (paper §4.1 ablation metric)."""
    xf = x.astype(jnp.float32)
    return jnp.sqrt(jnp.mean((fake_quant(x, bits, k_outliers).astype(jnp.float32) - xf) ** 2))
