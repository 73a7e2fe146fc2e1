"""Pallas TPU kernel: token-wise MHA (FlashAttention-style online softmax).

This is LightNobel's token-wise MHA (§5.4) on TPU: the score tensor — which
in PPM's triangular attention is the cubic (H, Ns, Ns, Ns) monster — never
leaves VMEM.  Supports:

  * additive pair bias (triangular attention's b_jk term) with batch
    broadcasting (bias batch = protein batch, q batch = protein x row),
  * GQA (Hq % Hkv == 0) via index-map head folding,
  * causal and sliding-window masks (LM archs),
  * kv_valid_len masking (decode steps with a partially-filled KV cache).

The kernel works on a head-major (B, H, S, D) view, so every block's last
two dims are (rows, D) — the TPU's (8, 128) tiling rule applies to those.
Grid = (B, Hq, nQ, nKV), KV innermost; the running max / denominator /
accumulator live in VMEM scratch and the output block is written once, on
the last KV step.  Block shapes default to (128, 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _flash_kernel(*refs, nkv: int, block_q: int, block_k: int,
                  causal: bool, window, scale: float, has_bias: bool,
                  has_kvlen: bool):
    q_ref, k_ref, v_ref, *rest = refs
    bias_ref = rest.pop(0) if has_bias else None
    kvlen_ref = rest.pop(0) if has_kvlen else None
    o_ref, m_ref, l_ref, acc_ref = rest

    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    q = q_ref[0, 0].astype(jnp.float32)                      # (BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)                      # (BK, D)
    v = v_ref[0, 0].astype(jnp.float32)                      # (BK, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if bias_ref is not None:
        s = s + bias_ref[0, 0].astype(jnp.float32)

    qpos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = jnp.ones(s.shape, jnp.bool_)
    if causal:
        ok &= kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    if kvlen_ref is not None:
        ok &= kpos < kvlen_ref[0]                            # (1, 1) bcast
    s = jnp.where(ok, s, NEG)

    m_prev = m_ref[...]                                      # (BQ, 1)
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(ok, p, 0.0)                                # kill fully-masked
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)

    @pl.when(j == nkv - 1)
    def _final():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softmax_scale",
                              "block_q", "block_k", "interpret"))
def flash_mha_pallas(q, k, v, bias=None, kv_valid_len=None, *,
                     causal=False, window=None, softmax_scale=None,
                     block_q=128, block_k=128, interpret: bool):
    """q (B,Sq,Hq,D); k,v (B,Skv,Hkv,D); bias (Bb,Hq,Sq,Skv); -> (B,Sq,Hq,D)."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = float(softmax_scale) if softmax_scale is not None else 1.0 / (d ** 0.5)

    bq = min(block_q, sq)
    bk = min(block_k, skv)
    pad_q, pad_k = (-sq) % bq, (-skv) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        if bias is not None:
            bias = jnp.pad(bias, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        if bias is not None:
            bias = jnp.pad(bias, ((0, 0), (0, 0), (0, 0), (0, pad_k)),
                           constant_values=NEG)
        if kv_valid_len is None:      # padded KV must be masked out
            kv_valid_len = jnp.full((b,), skv, jnp.int32)
    sqp, skvp = q.shape[1], k.shape[1]
    nq, nkv = sqp // bq, skvp // bk

    # head-major views: blocks are (1, 1, rows, D)
    qh, kh, vh = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
    has_bias = bias is not None
    has_kvlen = kv_valid_len is not None
    in_specs = [
        pl.BlockSpec((1, 1, bq, d), lambda b_, h, i_, j_: (b_, h, i_, 0)),
        pl.BlockSpec((1, 1, bk, d),
                     lambda b_, h, i_, j_: (b_, h // group, j_, 0)),
        pl.BlockSpec((1, 1, bk, d),
                     lambda b_, h, i_, j_: (b_, h // group, j_, 0)),
    ]
    args = [qh, kh, vh]
    if has_bias:
        bgroup = b // bias.shape[0]
        in_specs.append(pl.BlockSpec(
            (1, 1, bq, bk), lambda b_, h, i_, j_: (b_ // bgroup, h, i_, j_)))
        args.append(bias)
    if has_kvlen:
        # (B, 1, 1): the block's last two dims equal the array's
        kvl = kv_valid_len.reshape(b, 1, 1).astype(jnp.int32)
        in_specs.append(pl.BlockSpec((1, 1, 1),
                                     lambda b_, h, i_, j_: (b_, 0, 0)))
        args.append(kvl)

    kernel = functools.partial(
        _flash_kernel, nkv=nkv, block_q=bq, block_k=bk, causal=causal,
        window=window, scale=scale, has_bias=has_bias, has_kvlen=has_kvlen)
    o = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nkv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h, i_, j_: (b_, h, i_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sqp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(*args)
    return jnp.swapaxes(o, 1, 2)[:, :sq]
