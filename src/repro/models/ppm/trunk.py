"""Protein Folding Block (ESMFold folding-trunk / AF2 Evoformer style).

Implements the paper's Fig. 2(b) dataflow: a sequence-representation track
(B, Ns, Hm) and the Pair-Representation track (B, Ns, Ns, Hz) with

  * sequence attention with pair bias  + transition
  * outer-product-mean seq->pair update
  * Triangular Multiplication (outgoing + incoming)      [Fig. 6(a)]
  * Triangular Attention (starting + ending node)        [Fig. 6(b)]
  * pair transition

Every Pair-dataflow activation passes through the active quantization scheme
at a named site; the site names bind to AAQ's group table (core.policy).  The
sequence track is NOT quantized — matching the paper, which targets only the
Pair-Representation dataflow (§4.1).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.schemes import QuantScheme
from repro.kernels import dispatch
from repro.models import common as cm
from repro.parallel.sharding import constrain as _constrain


@dataclasses.dataclass(frozen=True)
class PPMConfig:
    blocks: int = 48
    hm: int = 1024          # sequence-representation hidden (ESMFold)
    hz: int = 128           # pair-representation hidden (paper: 128)
    seq_heads: int = 16
    pair_heads: int = 4     # head dim 32 — the RMPU PE-Lane native case
    tri_hidden: int = 128
    transition_factor: int = 4
    vocab: int = 23         # 20 aa + X + gap + mask
    relpos_bins: int = 65
    recycles: int = 1
    distogram_bins: int = 64
    ipa_iters: int = 4
    dtype: str = "float32"

    @property
    def pair_head_dim(self) -> int:
        return self.hz // self.pair_heads

    @property
    def np_dtype(self):
        return jnp.dtype(self.dtype)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------
def init_block(key, cfg: PPMConfig) -> cm.Params:
    ks = iter(jax.random.split(key, 40))
    hm, hz, th = cfg.hm, cfg.hz, cfg.tri_hidden
    f = cfg.transition_factor
    dt = cfg.np_dtype

    def d(i, o, bias=False, zero=False):
        fn = cm.dense_zero_init if zero else cm.dense_init
        return fn(next(ks), i, o, bias=bias, dtype=dt)

    def tri_mul():
        return {
            "ln_in": cm.ln_init(hz, dt),
            "a_proj": d(hz, th), "a_gate": d(hz, th),
            "b_proj": d(hz, th), "b_gate": d(hz, th),
            "ln_out": cm.ln_init(th, dt),
            "out": d(th, hz), "out_gate": d(hz, hz),
        }

    def tri_attn():
        return {
            "ln": cm.ln_init(hz, dt),
            "qkv": d(hz, 3 * hz),
            "bias": d(hz, cfg.pair_heads),
            "gate": d(hz, hz),
            "out": d(hz, hz),
        }

    return {
        "seq_attn": {
            "ln": cm.ln_init(hm, dt),
            "qkv": d(hm, 3 * hm, bias=True),
            "pair_bias_ln": cm.ln_init(hz, dt),
            "pair_bias": d(hz, cfg.seq_heads),
            "gate": d(hm, hm),
            "out": d(hm, hm),
        },
        "seq_trans": {
            "ln": cm.ln_init(hm, dt),
            "up": d(hm, f * hm, bias=True), "down": d(f * hm, hm, bias=True),
        },
        "opm": {  # outer-product-mean seq -> pair
            "ln": cm.ln_init(hm, dt),
            "a": d(hm, 32), "b": d(hm, 32),
            "out": d(32 * 32, hz, bias=True),
        },
        "tri_mul_out": tri_mul(),
        "tri_mul_in": tri_mul(),
        "tri_attn_start": tri_attn(),
        "tri_attn_end": tri_attn(),
        "pair_trans": {
            "ln": cm.ln_init(hz, dt),
            "up": d(hz, f * hz, bias=True), "down": d(f * hz, hz, bias=True),
        },
    }


# --------------------------------------------------------------------------
# padding-mask helpers
#
# ``mask`` is (B, N) bool — True at real tokens.  ``mask=None`` is the
# legacy unmasked path (bit-for-bit unchanged).  All masking is designed so
# real-token values are *bitwise* those of the unpadded forward: real
# entries are only ever multiplied by exactly 1.0 or summed with additive
# 0.0 / exact-zero padded contributions, never rescaled (key masking goes
# through cm.key_padding_bias for the same reason).
# --------------------------------------------------------------------------

# Sequence length at/above which triangular attention switches to the
# token-wise MHA path (flattened rows-as-batch; the cubic score tensor is
# never materialized).  Works at any batch size: the bias batch broadcast
# is block-wise (protein-major), matching the flattened row layout in both
# the XLA ref and the Pallas flash kernel.
CHUNKED_ATTN_LEN = 256


def _pair_mask(mask):
    """(B, N) bool -> (B, N, N, 1) float: 1.0 where both tokens are real."""
    m = (mask[:, :, None] & mask[:, None, :])[..., None]
    return m


# --------------------------------------------------------------------------
# pair ops (the paper's Fig. 6 dataflows, with AAQ sites)
# --------------------------------------------------------------------------
def tri_mul_apply(p, z, scheme: QuantScheme, outgoing: bool, sc: str,
                  mask=None):
    """Triangular multiplication. sc = site prefix ('tri_mul_out' etc.)."""
    z = scheme.act(z, f"{sc}.pre_ln")                       # Group A
    zl = cm.layernorm(p["ln_in"], z)
    zl = scheme.act(zl, f"{sc}.post_ln")                    # Group B
    a = (jax.nn.sigmoid(cm.dense(p["a_gate"], zl, scheme, f"{sc}.gate"))
         * cm.dense(p["a_proj"], zl, scheme, f"{sc}.post_ln"))
    b = (jax.nn.sigmoid(cm.dense(p["b_gate"], zl, scheme, f"{sc}.gate"))
         * cm.dense(p["b_proj"], zl, scheme, f"{sc}.post_ln"))
    a = scheme.act(a, f"{sc}.ab")                           # Group C
    b = scheme.act(b, f"{sc}.ab")
    if mask is not None:
        # zero padded pair rows so the k-contraction below only ever adds
        # exact zeros for padded k (real entries are multiplied by 1.0)
        pm = _pair_mask(mask).astype(a.dtype)
        a = a * pm
        b = b * pm
    eq = "bikc,bjkc->bijc" if outgoing else "bkic,bkjc->bijc"
    x = jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32)).astype(z.dtype)
    x = scheme.act(x, f"{sc}.prod_pre_ln")                  # Group A (large)
    xl = cm.layernorm(p["ln_out"], x)
    xl = scheme.act(xl, f"{sc}.post_ln")                    # Group B
    g = jax.nn.sigmoid(cm.dense(p["out_gate"], zl, scheme, f"{sc}.gate"))
    out = g * cm.dense(p["out"], xl, scheme, f"{sc}.post_ln")
    return scheme.act(out, f"{sc}.out")                     # Group C


def tri_attn_apply(p, z, scheme: QuantScheme, starting: bool, sc: str,
                   heads: int, mask=None):
    """Triangular attention; ending-node = starting-node on transposed pair."""
    if not starting:
        z = jnp.swapaxes(z, 1, 2)
    z = scheme.act(z, f"{sc}.pre_ln")                       # Group A
    zl = cm.layernorm(p["ln"], z)
    zl = scheme.act(zl, f"{sc}.post_ln")                    # Group B
    b_, n, _, hz = zl.shape
    dh = hz // heads
    qkv = cm.dense(p["qkv"], zl, scheme, f"{sc}.qkv_in")
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(b_, n, n, heads, dh)
    k = k.reshape(b_, n, n, heads, dh)
    v = v.reshape(b_, n, n, heads, dh)
    if mask is not None:
        # padded keys: zero v (their prob is already exactly 0 post-softmax,
        # but 0 * garbage must never become NaN)
        v = v * mask[:, None, :, None, None].astype(v.dtype)
    bias = cm.dense(p["bias"], zl, scheme, f"{sc}.post_ln")  # (B,N,N,H)
    # starting node: logits[b,h,i,j,k] = q_ij . k_ik + bias_jk
    if n >= CHUNKED_ATTN_LEN or dispatch.attention_is_pallas(n, n):
        # token-wise MHA (paper §5.4): rows are batch, the (N,N,N) score
        # tensor never materializes.  Dispatch routes the flattened call to
        # the Pallas flash kernel or the XLA-chunked ref; both broadcast
        # the (B,H,N,N) bias block-wise over the B*N protein-major rows,
        # so any batch size works.  Padding is a contiguous suffix
        # (serving buckets), so the key mask folds into kv_valid_len.
        kv_valid = None
        if mask is not None:
            lens = jnp.sum(mask.astype(jnp.int32), axis=-1)          # (B,)
            kv_valid = jnp.repeat(lens, n)                           # (B*n,)
        o = dispatch.attention(q.reshape(b_ * n, n, heads, dh),
                               k.reshape(b_ * n, n, heads, dh),
                               v.reshape(b_ * n, n, heads, dh),
                               bias=jnp.transpose(bias, (0, 3, 1, 2)),
                               kv_valid_len=kv_valid,
                               causal=False, q_chunk=512)
        o = o.reshape(b_, n, n, heads, dh).astype(z.dtype)
    else:
        logits = jnp.einsum("bijhd,bikhd->bhijk", q.astype(jnp.float32),
                            k.astype(jnp.float32)) / jnp.sqrt(jnp.float32(dh))
        logits = logits + jnp.transpose(bias, (0, 3, 1, 2))[:, :, None].astype(jnp.float32)
        if mask is not None:
            logits = logits + cm.key_padding_bias(mask)[:, None, None, None, :]
        probs = jax.nn.softmax(logits, axis=-1).astype(z.dtype)
        probs = scheme.act(probs, f"{sc}.probs")            # Group C
        o = jnp.einsum("bhijk,bikhd->bijhd", probs.astype(jnp.float32),
                       v.astype(jnp.float32)).astype(z.dtype)
    o = scheme.act(o.reshape(b_, n, n, hz), f"{sc}.av")     # Group C
    g = jax.nn.sigmoid(cm.dense(p["gate"], zl, scheme, f"{sc}.gate"))
    out = cm.dense(p["out"], g * o, scheme, f"{sc}.proj_in")
    if not starting:
        out = jnp.swapaxes(out, 1, 2)
    return out


def pair_transition_apply(p, z, scheme: QuantScheme, sc: str = "pair_trans"):
    z = scheme.act(z, f"{sc}.pre_ln")                       # Group A
    zl = cm.layernorm(p["ln"], z)
    zl = scheme.act(zl, f"{sc}.post_ln")                    # Group B
    h = jax.nn.relu(cm.dense(p["up"], zl, scheme, f"{sc}.post_ln"))
    h = scheme.act(h, f"{sc}.proj_in")                      # Group C
    return cm.dense(p["down"], h, scheme, f"{sc}.proj_in")


# --------------------------------------------------------------------------
# sequence ops (not quantized — paper quantizes only pair dataflow)
# --------------------------------------------------------------------------
def seq_attn_apply(p, s, z, heads: int, mask=None, pair_bias=None):
    """``pair_bias`` lets the chunked path supply a pre-built (B,N,N,H)
    bias table (see chunking.seq_pair_bias_chunked); the inline projection
    below is the legacy unchunked path, bit-for-bit unchanged."""
    b_, n, hm = s.shape
    dh = hm // heads
    sl = cm.layernorm(p["ln"], s)
    qkv = cm.dense(p["qkv"], sl)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(b_, n, heads, dh)
    k = k.reshape(b_, n, heads, dh)
    v = v.reshape(b_, n, heads, dh)
    if mask is not None:
        v = v * mask[:, :, None, None].astype(v.dtype)
    bias = pair_bias if pair_bias is not None else cm.dense(
        p["pair_bias"], cm.layernorm(p["pair_bias_ln"], z))
    bias = jnp.transpose(bias, (0, 3, 1, 2)).astype(jnp.float32)  # (B,H,N,N)
    if mask is not None:
        # additive key-padding fold keeps masking non-rescaling: real keys
        # get literal +0.0, padded keys underflow to exact 0 post-softmax
        bias = bias + cm.key_padding_bias(mask)[:, None, None, :]
    o = dispatch.attention(q, k, v, bias=bias)
    o = o.reshape(b_, n, hm).astype(s.dtype)
    g = jax.nn.sigmoid(cm.dense(p["gate"], sl))
    return cm.dense(p["out"], g * o)


def seq_transition_apply(p, s):
    return cm.dense(p["down"], jax.nn.relu(cm.dense(p["up"], cm.layernorm(p["ln"], s))))


def opm_apply(p, s):
    sl = cm.layernorm(p["ln"], s)
    a, b = cm.dense(p["a"], sl), cm.dense(p["b"], sl)       # (B,N,32)
    outer = jnp.einsum("bic,bjd->bijcd", a.astype(jnp.float32),
                       b.astype(jnp.float32)).astype(s.dtype)
    return cm.dense(p["out"], outer.reshape(*outer.shape[:3], -1))


# --------------------------------------------------------------------------
# one folding block
# --------------------------------------------------------------------------
def block_apply(p, s, z, cfg: PPMConfig, scheme: QuantScheme, mask=None):
    # every op that launches a Pallas kernel (flash attention, the AAQ
    # quantizer and matmul) runs under a named scope, so the device ops'
    # metadata names the layer that launched them
    with jax.named_scope("seq_attn"):
        s = s + seq_attn_apply(p["seq_attn"], s, z, cfg.seq_heads, mask=mask)
    s = s + seq_transition_apply(p["seq_trans"], s)
    z = z + opm_apply(p["opm"], s)
    with jax.named_scope("tri_mul_out"):
        z = z + tri_mul_apply(p["tri_mul_out"], z, scheme, True,
                              "tri_mul_out", mask=mask)
    with jax.named_scope("tri_mul_in"):
        z = z + tri_mul_apply(p["tri_mul_in"], z, scheme, False,
                              "tri_mul_in", mask=mask)
    with jax.named_scope("tri_attn_start"):
        z = z + tri_attn_apply(p["tri_attn_start"], z, scheme, True,
                               "tri_attn_start", cfg.pair_heads, mask=mask)
    with jax.named_scope("tri_attn_end"):
        z = z + tri_attn_apply(p["tri_attn_end"], z, scheme, False,
                               "tri_attn_end", cfg.pair_heads, mask=mask)
    with jax.named_scope("pair_trans"):
        z = z + pair_transition_apply(p["pair_trans"], z, scheme)
    return s, z


def init_trunk(key, cfg: PPMConfig) -> cm.Params:
    keys = jax.random.split(key, cfg.blocks)
    return jax.vmap(partial(init_block, cfg=cfg))(keys)     # stacked for scan


def trunk_apply(stacked, s, z, cfg: PPMConfig, scheme: QuantScheme,
                remat: bool = False, mask=None, chunk_size: int | None = None):
    """``chunk_size`` routes every block through the row-chunked pair stack
    (repro.models.ppm.chunking): same ops, same sites, O(N·chunk) slabs
    instead of O(N²).  None/0 is the legacy unchunked path."""
    if chunk_size:
        from repro.models.ppm import chunking as ck   # imports this module

        def body(carry, p):
            s_, z_ = carry
            s_, z_ = ck.block_apply_chunked(p, s_, z_, cfg, scheme,
                                            chunk_size, mask=mask)
            return (_constrain(s_, "seq_track"), _constrain(z_, "pair")), None
    else:
        def body(carry, p):
            s_, z_ = carry
            s_, z_ = block_apply(p, s_, z_, cfg, scheme, mask=mask)
            return (_constrain(s_, "seq_track"), _constrain(z_, "pair")), None

    if remat:
        body = jax.checkpoint(body)
    (s, z), _ = jax.lax.scan(body, (s, z), stacked)
    return s, z
