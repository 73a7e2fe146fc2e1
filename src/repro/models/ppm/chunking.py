"""Row-chunked pair-stack execution — the model half of the long-fold tier.

The trunk's pair ops each materialize O(N²·H) activations per step; at
N ≥ 2,000 one block's working set alone busts any single device.  This
module re-expresses every pair-stack op (`tri_mul_apply`, `tri_attn_apply`,
`pair_transition_apply`, the OPM update, and seq-attention's pair-bias
projection) as a row-chunked scan over the pair tensor's i axis: one
(B, chunk, N, H) slab is in flight at a time, so the per-step peak drops
O(N²·H) → O(N·chunk·H) plus a small set of *resident* full-width tensors
(the residual stream itself, tri-mul's partner operand, the attention-bias
tables) that the serving-side memory planner prices explicitly
(`repro.serving.longfold`).

Numerical contract (what `tests/test_chunking.py` gates):

  * FP schemes — chunked output matches unchunked to allclose(1e-4); in
    practice bitwise, because every op is row-local: layernorm/dense/gating
    reduce over the channel axis only, the k-contractions keep the same
    extent and operand order, and the token-wise attention path issues the
    *same* per-row flattened calls the unchunked path does (block-wise bias
    broadcast is protein-major, so a (B·chunk)-row call addresses the same
    bias entries as the (B·N)-row call).
  * AAQ — `AAQScheme.act` quantizes per token over the channel axis, so a
    chunked slab quantizes exactly as its slice of the full tensor; parity
    is TM-score-gated (≥ 0.995) like the placement tier.
  * Schemes with tensor- or channel-wide statistics (ptq4protein's tensor
    max, tender/llm_int8 channel maxima, smoothquant's all-token max) are
    NOT chunk-exact: their calibration would see one chunk instead of the
    full tensor.  The planner still admits them chunked, but parity is only
    gated for the fp/aaq schemes the serving tier ships.

Chunking composes with GSPMD sharding: the serving rules shard the pair
tensor's *j* axis (`P(None, None, MODEL, None)`), this scan chunks the *i*
axis, so a chunked executable lowers under the same mesh rules as one
traced program — no resharding between chunks.

The chunk scan uses `jax.lax.map` (the same idiom as `mha_chunked` in
`repro.kernels.flash_attention.ref`), so compile time stays flat in N/chunk.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.schemes import QuantScheme
from repro.kernels import dispatch
from repro.models import common as cm
from repro.models.ppm import trunk as tk


def effective_chunk_size(n: int, chunk: int) -> int:
    """Largest divisor of ``n`` that is <= ``chunk``.

    Chunks must tile the row axis exactly (no ragged tail slab, which would
    recompile per remainder).  Serving buckets are powers of two, so a
    power-of-two request degrades gracefully; ``n`` prime degrades to 1.
    """
    c = max(1, min(int(chunk), int(n)))
    while n % c:
        c -= 1
    return c


def _scan_rows(fn, slabs, n: int, chunk: int):
    """Map ``fn`` over row-chunks of a pytree of arrays.

    Every leaf of ``slabs`` has the row axis at position 1 (length ``n``);
    ``fn`` receives the pytree with that axis length ``chunk`` and returns
    one (B, chunk, ...) array.  Output is reassembled to (B, n, ...).
    """
    def split(x):
        b = x.shape[0]
        return jnp.moveaxis(x.reshape(b, n // chunk, chunk, *x.shape[2:]), 1, 0)

    xs = jax.tree_util.tree_map(split, slabs)
    ys = jax.lax.map(fn, xs)
    return jnp.moveaxis(ys, 0, 1).reshape(ys.shape[1], n, *ys.shape[3:])


def _pair_ln(p, z_rows, scheme: QuantScheme, sc: str, key: str):
    """pre_ln -> layernorm -> post_ln on a row slab, same sites as unchunked."""
    z_rows = scheme.act(z_rows, f"{sc}.pre_ln")             # Group A
    zl = cm.layernorm(p[key], z_rows)
    return scheme.act(zl, f"{sc}.post_ln")                  # Group B


# --------------------------------------------------------------------------
# triangular multiplication
# --------------------------------------------------------------------------
def _tri_mul_ab(p, z_rows, scheme: QuantScheme, sc: str, proj: str, gate: str,
                row_mask=None, mask=None):
    """The a/b operand of tri-mul for one row slab; returns (ab, zl)."""
    zl = _pair_ln(p, z_rows, scheme, sc, "ln_in")
    ab = (jax.nn.sigmoid(cm.dense(p[gate], zl, scheme, f"{sc}.gate"))
          * cm.dense(p[proj], zl, scheme, f"{sc}.post_ln"))
    ab = scheme.act(ab, f"{sc}.ab")                         # Group C
    if mask is not None:
        pm = (row_mask[:, :, None] & mask[:, None, :])[..., None]
        ab = ab * pm.astype(ab.dtype)
    return ab, zl


def tri_mul_chunked(p, z, scheme: QuantScheme, outgoing: bool, sc: str,
                    chunk: int, mask=None):
    """Row-chunked triangular multiplication.

    The partner operand (``b`` of the k-contraction) is full-width and
    resident — it is the price of chunking tri-mul, and the admission
    controller's chunked estimator charges it at the scheme's ``{sc}.ab``
    bits.  It is built row-slab by row-slab so the hz-wide layernorm
    intermediate never materializes at O(N²).
    """
    b_, n = z.shape[:2]
    c = effective_chunk_size(n, chunk)

    def partner(slab):
        mc = slab[-1] if mask is not None else None
        bb, _ = _tri_mul_ab(p, slab[0], scheme, sc, "b_proj", "b_gate",
                            row_mask=mc, mask=mask)
        return bb

    pslabs = (z,) if mask is None else (z, mask)
    b_full = _scan_rows(partner, pslabs, n, c)              # (B,N,N,th)

    def rows(slab):
        zc = slab[0]
        mc = slab[-1] if mask is not None else None
        if outgoing:
            # x[b,i,j,c] = sum_k a[b,i,k,c] * b[b,j,k,c]: a is row-local.
            ac, zl = _tri_mul_ab(p, zc, scheme, sc, "a_proj", "a_gate",
                                 row_mask=mc, mask=mask)
            x = jnp.einsum("bikc,bjkc->bijc", ac.astype(jnp.float32),
                           b_full.astype(jnp.float32)).astype(zc.dtype)
        else:
            # x[b,i,j,c] = sum_k a[b,k,i,c] * b[b,k,j,c]: the a columns for
            # rows i come from the transposed slab (same values, (i,k)
            # layout), while the output gate reads zl of the plain rows.
            ac, _ = _tri_mul_ab(p, slab[1], scheme, sc, "a_proj", "a_gate",
                                row_mask=mc, mask=mask)
            x = jnp.einsum("bikc,bkjc->bijc", ac.astype(jnp.float32),
                           b_full.astype(jnp.float32)).astype(zc.dtype)
            zl = _pair_ln(p, zc, scheme, sc, "ln_in")
        x = scheme.act(x, f"{sc}.prod_pre_ln")              # Group A (large)
        xl = cm.layernorm(p["ln_out"], x)
        xl = scheme.act(xl, f"{sc}.post_ln")                # Group B
        g = jax.nn.sigmoid(cm.dense(p["out_gate"], zl, scheme, f"{sc}.gate"))
        out = g * cm.dense(p["out"], xl, scheme, f"{sc}.post_ln")
        return scheme.act(out, f"{sc}.out")                 # Group C

    slabs = [z] if outgoing else [z, jnp.swapaxes(z, 1, 2)]
    if mask is not None:
        slabs.append(mask)
    return _scan_rows(rows, tuple(slabs), n, c)


# --------------------------------------------------------------------------
# triangular attention
# --------------------------------------------------------------------------
def tri_attn_chunked(p, z, scheme: QuantScheme, starting: bool, sc: str,
                     heads: int, chunk: int, mask=None):
    """Row-chunked triangular attention.

    The (B,N,N,heads) bias table is full-width and resident (heads is
    small); each row chunk then issues exactly the call the unchunked op
    would: the token-wise path flattens (B·chunk) rows through
    ``dispatch.attention`` with the same block-broadcast bias, and the
    einsum path keeps the explicit softmax + ``{sc}.probs`` site.  Branch
    selection uses the FULL n, not the chunk — chunking must never change
    which kernel (and which AAQ sites) a given bucket runs.
    """
    if not starting:
        z = jnp.swapaxes(z, 1, 2)
    b_, n, _, hz = z.shape
    c = effective_chunk_size(n, chunk)
    dh = hz // heads

    def bias_rows(slab):
        zl = _pair_ln(p, slab[0], scheme, sc, "ln")
        return cm.dense(p["bias"], zl, scheme, f"{sc}.post_ln")

    bias = _scan_rows(bias_rows, (z,), n, c)                # (B,N,N,H)
    bias_t = jnp.transpose(bias, (0, 3, 1, 2))              # (B,H,N,N)

    tokenwise = n >= tk.CHUNKED_ATTN_LEN or dispatch.attention_is_pallas(n, n)
    lens = (jnp.sum(mask.astype(jnp.int32), axis=-1)        # (B,)
            if mask is not None else None)

    def rows(slab):
        zc = slab[0]                                        # (B,C,N,hz)
        zl = _pair_ln(p, zc, scheme, sc, "ln")
        qkv = cm.dense(p["qkv"], zl, scheme, f"{sc}.qkv_in")
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b_, c, n, heads, dh)
        k = k.reshape(b_, c, n, heads, dh)
        v = v.reshape(b_, c, n, heads, dh)
        if mask is not None:
            v = v * mask[:, None, :, None, None].astype(v.dtype)
        if tokenwise:
            kv_valid = jnp.repeat(lens, c) if mask is not None else None
            o = dispatch.attention(q.reshape(b_ * c, n, heads, dh),
                                   k.reshape(b_ * c, n, heads, dh),
                                   v.reshape(b_ * c, n, heads, dh),
                                   bias=bias_t,
                                   kv_valid_len=kv_valid,
                                   causal=False, q_chunk=512)
            o = o.reshape(b_, c, n, heads, dh).astype(zc.dtype)
        else:
            logits = jnp.einsum("bijhd,bikhd->bhijk", q.astype(jnp.float32),
                                k.astype(jnp.float32)) / jnp.sqrt(jnp.float32(dh))
            logits = logits + bias_t[:, :, None].astype(jnp.float32)
            if mask is not None:
                logits = logits + cm.key_padding_bias(mask)[:, None, None, None, :]
            probs = jax.nn.softmax(logits, axis=-1).astype(zc.dtype)
            probs = scheme.act(probs, f"{sc}.probs")        # Group C
            o = jnp.einsum("bhijk,bikhd->bijhd", probs.astype(jnp.float32),
                           v.astype(jnp.float32)).astype(zc.dtype)
        o = scheme.act(o.reshape(b_, c, n, hz), f"{sc}.av")  # Group C
        g = jax.nn.sigmoid(cm.dense(p["gate"], zl, scheme, f"{sc}.gate"))
        return cm.dense(p["out"], g * o, scheme, f"{sc}.proj_in")

    out = _scan_rows(rows, (z,), n, c)
    if not starting:
        out = jnp.swapaxes(out, 1, 2)
    return out


# --------------------------------------------------------------------------
# pair transition / OPM / seq-attention pair bias
# --------------------------------------------------------------------------
def pair_transition_chunked(p, z, scheme: QuantScheme, chunk: int,
                            sc: str = "pair_trans"):
    """Pair transition is elementwise over (i, j): chunk rows directly."""
    n = z.shape[1]
    c = effective_chunk_size(n, chunk)
    return _scan_rows(
        lambda slab: tk.pair_transition_apply(p, slab[0], scheme, sc),
        (z,), n, c)


def opm_chunked(p, s, chunk: int):
    """Outer-product-mean without the (B,N,N,32·32) slab: the a/b vectors
    are linear in N, only the per-chunk outer product materializes."""
    b_, n, _ = s.shape
    c = effective_chunk_size(n, chunk)
    sl = cm.layernorm(p["ln"], s)
    a, b = cm.dense(p["a"], sl), cm.dense(p["b"], sl)       # (B,N,32)

    def rows(slab):
        outer = jnp.einsum("bic,bjd->bijcd", slab[0].astype(jnp.float32),
                           b.astype(jnp.float32)).astype(s.dtype)
        return cm.dense(p["out"], outer.reshape(*outer.shape[:3], -1))

    return _scan_rows(rows, (a,), n, c)


def seq_pair_bias_chunked(p, z, chunk: int):
    """Sequence attention's (B,N,N,seq_heads) pair bias, built row-slab by
    row-slab so the full hz-wide ln(z) intermediate never materializes."""
    n = z.shape[1]
    c = effective_chunk_size(n, chunk)
    return _scan_rows(
        lambda slab: cm.dense(p["pair_bias"],
                              cm.layernorm(p["pair_bias_ln"], slab[0])),
        (z,), n, c)


# --------------------------------------------------------------------------
# one folding block, chunked
# --------------------------------------------------------------------------
def block_apply_chunked(p, s, z, cfg, scheme: QuantScheme, chunk: int,
                        mask=None):
    """`trunk.block_apply` with every O(N²·H) pair op row-chunked.

    Op order, residual structure, and quantization sites are identical to
    the unchunked block — only the materialization schedule changes.
    """
    pb = seq_pair_bias_chunked(p["seq_attn"], z, chunk)
    # the named scopes of ``trunk.block_apply``, around the same ops
    with jax.named_scope("seq_attn"):
        s = s + tk.seq_attn_apply(p["seq_attn"], s, z, cfg.seq_heads,
                                  mask=mask, pair_bias=pb)
    s = s + tk.seq_transition_apply(p["seq_trans"], s)
    z = z + opm_chunked(p["opm"], s, chunk)
    with jax.named_scope("tri_mul_out"):
        z = z + tri_mul_chunked(p["tri_mul_out"], z, scheme, True,
                                "tri_mul_out", chunk, mask=mask)
    with jax.named_scope("tri_mul_in"):
        z = z + tri_mul_chunked(p["tri_mul_in"], z, scheme, False,
                                "tri_mul_in", chunk, mask=mask)
    with jax.named_scope("tri_attn_start"):
        z = z + tri_attn_chunked(p["tri_attn_start"], z, scheme, True,
                                 "tri_attn_start", cfg.pair_heads, chunk,
                                 mask=mask)
    with jax.named_scope("tri_attn_end"):
        z = z + tri_attn_chunked(p["tri_attn_end"], z, scheme, False,
                                 "tri_attn_end", cfg.pair_heads, chunk,
                                 mask=mask)
    with jax.named_scope("pair_trans"):
        z = z + pair_transition_chunked(p["pair_trans"], z, scheme, chunk)
    return s, z
