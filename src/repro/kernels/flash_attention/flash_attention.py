"""Pallas TPU kernel: token-wise MHA (FlashAttention-style online softmax).

This is LightNobel's token-wise MHA (§5.4) on TPU: the score tensor — which
in PPM's triangular attention is the cubic (H, Ns, Ns, Ns) monster — never
leaves VMEM.  Supports:

  * additive pair bias (triangular attention's b_jk term) with batch
    broadcasting (bias batch = protein batch, q batch = protein x row),
  * GQA (Hq % Hkv == 0): the kernel sees one KV head per q head,
  * causal and sliding-window masks (LM archs),
  * kv_valid_len masking (decode steps with a partially-filled KV cache).

Layout: the kernel works on lane-dense (B, 1, S, H·D) views of the
(B, S, H, D) operands, a free reshape.  Inside a lane tile (128 lanes, or
the whole H·D where that is narrower) each head is split off by zeroing
the other heads' lanes of q for q·k and of v for p·v: on a 128x128 MXU
that takes the passes a D-wide dot takes, and no transpose is needed.

Grid = (head groups, q blocks, row blocks, KV blocks), KV innermost; the
running max / denominator / accumulator live in VMEM scratch and the
output block is written once, on the last KV step.  ``plan`` picks the
block parameters from the call's shapes and a VMEM budget: rows per step,
heads per step, the q block and the KV block.  Where the padded key range
fits, the KV axis has one step (the online softmax is one exact pass), and
the row blocks, innermost then, share one bias block: a protein's bias is
fetched once per (head group, q block), not once per row.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30

VMEM_LIMIT = 32 * 2 ** 20    # vmem_limit_bytes of every call
VMEM_BUDGET = 24 * 2 ** 20   # what a plan's blocks may take of it
MAX_ROWS = 128               # batch rows a grid step, at most
BLOCK_Q = (256, 128)         # q block candidates for long queries
BLOCK_K = (512, 256, 128)    # KV block candidates when the range streams


def _up(n: int, m: int) -> int:
    return -(-n // m) * m


def _padded_len(n: int) -> int:
    """``n`` padded for blocking: kept up to 128, else to a 128 multiple."""
    return n if n <= 128 else _up(n, 128)


def _pad_free_rows(b: int, most: int) -> int:
    """Rows a step, at most ``most``: a divisor of ``b`` if one is at least
    half of ``most`` (no padded rows), else as few padded rows as the step
    count allows."""
    for r in range(most, (most - 1) // 2, -1):
        if b % r == 0:
            return r
    return -(-b // -(-b // most))


@dataclass(frozen=True)
class FlashPlan:
    """One call's blocking.  ``rows`` batch rows, ``heads`` heads, a
    ``block_q`` x ``block_k`` score tile a grid step; the padded lengths
    ``sq`` and ``skv``; ``tile`` lanes per head-masked dot."""
    rows: int
    heads: int
    block_q: int
    block_k: int
    sq: int
    skv: int
    tile: int

    @property
    def kv_resident(self) -> bool:
        """The whole key range is one block: one KV step, one exact pass."""
        return self.block_k == self.skv


def _vmem_bytes(rows, heads, bq, bk, d, itemsize, bias_itemsize, bias_rows,
                streamed):
    """VMEM a grid step takes: double-buffered blocks, a head's f32 score
    tiles in flight, and the online-softmax scratch of a streamed call."""
    lanes = _up(heads * d, 128)
    sq8, sk = _up(bq, 8), _up(bk, 128)
    block = rows * 2 * (sq8 + _up(bk, 8)) * lanes * itemsize   # q, o, k, v
    block += rows * 8 * 128 * 4                                 # kv lengths
    if bias_itemsize:
        block += bias_rows * heads * sq8 * sk * bias_itemsize
    total = 2 * block + 4 * sq8 * sk * 4 + 3 * sq8 * lanes * 4
    if streamed:
        total += rows * (sq8 * lanes + 2 * heads * sq8 * 128) * 4
    return total


def plan(q, k, bias=None, *, causal=False, window=None, block_q=None,
         block_k=None) -> FlashPlan:
    """Block parameters of ``flash_mha_pallas(q, k, v, bias, ...)``, from
    the operands' shapes and dtypes (arrays or ShapeDtypeStructs) and a VMEM
    budget (``VMEM_BUDGET``).  ``block_q`` / ``block_k`` cap the q and KV
    blocks.

    In order of preference: the whole padded key range as one block (not
    for causal or windowed calls, which stream), every head of the call,
    the larger q block, the most rows.  Heads go in groups that fill whole
    lane tiles; rows in blocks that never straddle two bias entries.
    """
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    itemsize = jnp.dtype(q.dtype).itemsize
    bias_itemsize = jnp.dtype(bias.dtype).itemsize if bias is not None else 0
    rep = b // bias.shape[0] if bias is not None and bias.shape[0] > 1 else 0

    width = hq * d
    tile = math.lcm(d, 128)
    if width % tile:
        tile = width
    per_tile = tile // d
    head_groups = [g for g in range(hq, 0, -per_tile) if hq % g == 0]

    sqp = _padded_len(sq)
    q_blocks = [sqp] if sqp <= BLOCK_Q[0] else \
        [c for c in BLOCK_Q if sqp % c == 0]
    if block_q is not None and block_q < sqp:
        q_blocks = [block_q]
        sqp = _up(sq, block_q)

    skvp = _padded_len(skv)
    kv_blocks = [] if causal or window is not None else [skvp]
    kv_blocks += [c for c in BLOCK_K if c < skvp and skvp % c == 0]
    if block_k is not None and block_k < skvp:
        kv_blocks = [block_k]
        skvp = _up(skv, block_k)
    kv_blocks = kv_blocks or [skvp]

    if rep:      # a row block lies inside one bias entry, or holds whole ones
        row_counts = [r for r in range(min(b, MAX_ROWS), 0, -1)
                      if b % r == 0 and (rep % r == 0 or r % rep == 0)]
    else:        # one bias (or none) for all rows: pad the rows
        row_counts = list(range(min(b, MAX_ROWS), 0, -1))

    def fits(rows, heads, bq, bk):
        bias_rows = max(1, rows // rep) if rep else 1
        return _vmem_bytes(rows, heads, bq, bk, d, itemsize, bias_itemsize,
                           bias_rows, bk < skvp) <= VMEM_BUDGET

    for bk in kv_blocks:
        for heads in head_groups:
            for bq in q_blocks:
                for rows in row_counts:
                    if fits(rows, heads, bq, bk):
                        if not rep:
                            rows = _pad_free_rows(b, rows)
                        return FlashPlan(rows, heads, bq, bk, sqp, skvp, tile)
    # nothing fits the budget: the smallest blocks, and let the compiler say
    return FlashPlan(row_counts[-1], head_groups[-1], q_blocks[-1],
                     kv_blocks[-1], sqp, skvp, tile)


def _flash_kernel(*refs, nkv: int, block_q: int, block_k: int, rows: int,
                  heads: int, d: int, tile: int, rep: int, causal: bool,
                  window, scale: float, has_bias: bool, has_kvlen: bool):
    q_ref, k_ref, v_ref, *rest = refs
    bias_ref = rest.pop(0) if has_bias else None
    kvlen_ref = rest.pop(0) if has_kvlen else None
    o_ref, *scratch = rest                  # m, l, acc when the KV streams

    i, j = pl.program_id(1), pl.program_id(3)
    shape = (block_q, block_k)
    qpos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    fixed = None
    if causal:
        fixed = kpos <= qpos
    if window is not None:
        inside = kpos > qpos - window
        fixed = inside if fixed is None else fixed & inside
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1) // d
    per_tile = tile // d
    streamed = nkv > 1

    if streamed:
        m_ref, l_ref, acc_ref = scratch

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def row(r, carry):
        ok = fixed
        if has_kvlen:
            valid = kpos < kvlen_ref[r]                      # (1, 1) bcast
            ok = valid if ok is None else ok & valid
        bias_row = jax.lax.div(r, rep) if rep < rows else 0
        for t in range(heads * d // tile):
            lanes = pl.ds(t * tile, tile)
            q = q_ref[r, 0, :, lanes]                        # (BQ, tile)
            k = k_ref[r, 0, :, lanes]                        # (BK, tile)
            v = v_ref[r, 0, :, lanes].astype(jnp.float32)
            pv_sum = alpha_lanes = None
            for hh in range(per_tile):
                h = t * per_tile + hh
                mine = lane_head == hh
                s = jax.lax.dot_general(
                    jnp.where(mine, q, 0), k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if has_bias:
                    s = s + bias_ref[bias_row, h].astype(jnp.float32)
                if ok is not None:
                    s = jnp.where(ok, s, NEG)
                m_prev = m_ref[r, h] if streamed else NEG
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                if ok is not None:
                    p = jnp.where(ok, p, 0.0)                # kill fully-masked
                l_new = jnp.sum(p, axis=-1, keepdims=True)
                pv = jax.lax.dot(p, jnp.where(mine, v, 0.0),
                                 preferred_element_type=jnp.float32)
                if streamed:
                    alpha = jnp.exp(m_prev - m_new)
                    l_ref[r, h] = alpha * l_ref[r, h] + l_new
                    m_ref[r, h] = m_new
                    a = jnp.where(mine, alpha, 0.0)
                    alpha_lanes = a if alpha_lanes is None else alpha_lanes + a
                else:
                    pv = pv / jnp.maximum(l_new, 1e-30)
                pv_sum = pv if pv_sum is None else pv_sum + pv
            if streamed:
                acc_ref[r, :, lanes] = acc_ref[r, :, lanes] * alpha_lanes \
                    + pv_sum
            else:
                o_ref[r, 0, :, lanes] = pv_sum.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows, row, 0)

    if streamed:
        @pl.when(j == nkv - 1)
        def _final():
            def out_row(r, carry):
                for t in range(heads * d // tile):
                    lanes = pl.ds(t * tile, tile)
                    l_lanes = None
                    for hh in range(per_tile):
                        lh = jnp.where(lane_head == hh,
                                       l_ref[r, t * per_tile + hh], 0.0)
                        l_lanes = lh if l_lanes is None else l_lanes + lh
                    o_ref[r, 0, :, lanes] = (
                        acc_ref[r, :, lanes] / jnp.maximum(l_lanes, 1e-30)
                    ).astype(o_ref.dtype)
                return carry

            jax.lax.fori_loop(0, rows, out_row, 0)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softmax_scale",
                              "block_q", "block_k", "interpret"))
def flash_mha_pallas(q, k, v, bias=None, kv_valid_len=None, *,
                     causal=False, window=None, softmax_scale=None,
                     block_q=None, block_k=None, interpret: bool):
    """q (B,Sq,Hq,D); k,v (B,Skv,Hkv,D); bias (Bb,Hq,Sq,Skv); -> (B,Sq,Hq,D).

    ``block_q`` / ``block_k`` cap the blocks ``plan`` picks (tests use
    them to force several q and KV steps)."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    assert hq % hkv == 0
    if hkv != hq:
        k = jnp.repeat(k, hq // hkv, axis=2)
        v = jnp.repeat(v, hq // hkv, axis=2)
    scale = float(softmax_scale) if softmax_scale is not None else 1.0 / (d ** 0.5)
    fp = plan(q, k, bias, causal=causal, window=window, block_q=block_q,
              block_k=block_k)
    bq, bk, rows, heads = fp.block_q, fp.block_k, fp.rows, fp.heads

    pad_b = (-b) % rows
    pad_q, pad_k = fp.sq - sq, fp.skv - skv
    q = jnp.pad(q, ((0, pad_b), (0, pad_q), (0, 0), (0, 0)))
    k, v = (jnp.pad(a, ((0, pad_b), (0, pad_k), (0, 0), (0, 0)))
            for a in (k, v))
    if pad_k and kv_valid_len is None:      # padded KV must be masked out
        kv_valid_len = jnp.full((b,), skv, jnp.int32)
    bp = b + pad_b
    # lane-dense views: blocks are (rows, 1, positions, heads*D)
    qf, kf, vf = (a.reshape(bp, 1, a.shape[1], hq * d) for a in (q, k, v))

    has_bias = bias is not None
    has_kvlen = kv_valid_len is not None
    ng, nq, nr, nkv = hq // heads, fp.sq // bq, bp // rows, fp.skv // bk
    in_specs = [
        pl.BlockSpec((rows, 1, bq, heads * d),
                     lambda g, i, r, j: (r, 0, i, g)),
        pl.BlockSpec((rows, 1, bk, heads * d),
                     lambda g, i, r, j: (r, 0, j, g)),
        pl.BlockSpec((rows, 1, bk, heads * d),
                     lambda g, i, r, j: (r, 0, j, g)),
    ]
    args = [qf, kf, vf]
    rep = bp                                # rows sharing one bias entry
    if has_bias:
        bias = jnp.pad(bias, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        bias = jnp.pad(bias, ((0, 0), (0, 0), (0, 0), (0, pad_k)),
                       constant_values=NEG)
        if bias.shape[0] > 1:
            rep = b // bias.shape[0]
        bias_rows = max(1, rows // rep)
        in_specs.append(pl.BlockSpec(
            (bias_rows, heads, bq, bk),
            lambda g, i, r, j: (r * rows // rep // bias_rows, g, i, j)))
        args.append(bias)
    if has_kvlen:
        # (B, 1, 1): the block's last two dims equal the array's
        kvl = jnp.pad(kv_valid_len.astype(jnp.int32), (0, pad_b))
        in_specs.append(pl.BlockSpec((rows, 1, 1),
                                     lambda g, i, r, j: (r, 0, 0)))
        args.append(kvl.reshape(bp, 1, 1))

    scratch = []
    if nkv > 1:
        scratch = [pltpu.VMEM((rows, heads, bq, 1), jnp.float32),
                   pltpu.VMEM((rows, heads, bq, 1), jnp.float32),
                   pltpu.VMEM((rows, bq, heads * d), jnp.float32)]
    kernel = functools.partial(
        _flash_kernel, nkv=nkv, block_q=bq, block_k=bk, rows=rows,
        heads=heads, d=d, tile=fp.tile, rep=rep, causal=causal,
        window=window, scale=scale, has_bias=has_bias, has_kvlen=has_kvlen)
    o = pl.pallas_call(
        kernel,
        grid=(ng, nq, nr, nkv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rows, 1, bq, heads * d),
                               lambda g, i, r, j: (r, 0, i, g)),
        out_shape=jax.ShapeDtypeStruct(qf.shape, qf.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*args)
    return o.reshape(bp, fp.sq, hq, d)[:b, :sq]
