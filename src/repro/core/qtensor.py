"""QTensor: the packed token-wise quantized activation container.

Mirrors LightNobel's HBM layout (Fig. 7): per token-block the memory holds
``inliers | outlier values | scaling factor | outlier indices``.  On TPU, a
pytree of separate arrays *is* that layout — each leaf is one contiguous HBM
buffer, and BlockSpecs stream token blocks of each buffer into VMEM together.

Semantics (paper §4.1):
  * token          = the trailing-axis vector of the activation (Hz in PPM).
  * inliers        = uniform symmetric INT4/INT8, per-token dynamic scale
                     sigma = max|inlier| / (2^(m-1) - 1).
  * outliers       = the k largest-|x| entries per token, kept at 16-bit and
                     *not* sharing sigma (the paper stores them in fixed-point
                     so "outliers do not require dequantization"; the TPU
                     adaptation stores them as bf16 — same width, MXU/VPU
                     native).  Inlier slots at outlier positions hold 0.
  * INT4 packing   = two nibbles per int8 carrier byte (low nibble = even
                     column), unpacked in-kernel; HBM traffic is what matters.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

INT4_MAX = 7
INT8_MAX = 127


@partial(jax.tree_util.register_dataclass,
         data_fields=("inliers", "scales", "outlier_values", "outlier_idx"),
         meta_fields=("bits", "k_outliers", "feature_dim", "orig_dtype"))
@dataclasses.dataclass(frozen=True)
class QTensor:
    """Token-wise quantized activation. Token axis = -1 of the original."""

    inliers: jax.Array          # int8; (..., H) for 8-bit, (..., H//2) packed for 4-bit
    scales: jax.Array           # f32 (..., 1) per-token sigma
    outlier_values: jax.Array   # bf16 (..., k)  (k == 0 -> trailing dim 0)
    outlier_idx: jax.Array      # int32 (..., k)
    bits: int                   # 4 or 8 (inlier precision)
    k_outliers: int             # static per policy group (paper DSE: 4 / 4 / 0)
    feature_dim: int            # H of the original activation
    orig_dtype: jnp.dtype       # dtype to dequantize back to

    @property
    def token_shape(self):
        return self.scales.shape[:-1]

    @property
    def shape(self):
        return (*self.token_shape, self.feature_dim)

    def nbytes(self) -> int:
        """Exact packed HBM footprint in bytes (drives the Table-1 bench)."""
        return (self.inliers.size * self.inliers.dtype.itemsize
                + self.scales.size * self.scales.dtype.itemsize
                + self.outlier_values.size * self.outlier_values.dtype.itemsize
                + self.outlier_idx.size * self.outlier_idx.dtype.itemsize)


def qmax(bits: int) -> int:
    return (1 << (bits - 1)) - 1


def pack_int4(q: jax.Array) -> jax.Array:
    """Pack int8 values in [-8,7] pairwise into nibble-packed int8 carriers."""
    assert q.shape[-1] % 2 == 0, "int4 packing needs an even feature dim"
    h, ax = q.shape[-1], q.ndim - 1
    # strided lax slices: a ``q[..., 0::2]`` index lowers to a gather on TPU
    lo = jax.lax.slice_in_dim(q, 0, h, stride=2, axis=ax) & 0x0F
    hi = (jax.lax.slice_in_dim(q, 1, h, stride=2, axis=ax) & 0x0F) << 4
    return (lo | hi).astype(jnp.int8)


def unpack_int4(p: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4`; arithmetic shifts restore the sign."""
    lo = (p << 4) >> 4                      # sign-extend low nibble
    hi = p >> 4                             # arithmetic shift: sign-extends
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(*p.shape[:-1], p.shape[-1] * 2).astype(jnp.int8)
