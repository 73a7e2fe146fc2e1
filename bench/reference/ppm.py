"""Plain reference of the fold model: one sequence at a time, in float32.

This module is the yardstick that decides a run's ``correct``.  It imports
nothing of the program under test: it reads the configuration file's sizes
and AAQ site table, and the parameter tree the benchmark made from the seed
(``bench/weights.py``), and computes, for one sequence, what the served
fold must return:

  * the input embedding (amino-acid embedding, left/right outer sum and the
    clipped relative-position embedding);
  * ``blocks`` folding blocks in ESMFold's order: sequence attention with a
    pair bias, sequence transition, outer-product mean, triangular
    multiplication outgoing then incoming, triangular attention around the
    starting then the ending node, pair transition;
  * the structure module (4 iterations of pair-biased attention with a
    point-distance term and a coordinate update), and the distogram head on
    the symmetrised pair track.

Under the ``lightnobel_aaq`` scheme every pair-track activation is
fake-quantized at its site by the configuration's group table: token-wise
symmetric quantization of the inliers with the k largest-magnitude entries
kept as they are, and every AAQ linear quantizes its input by its own
site's group before the product.  The sequence track is not quantized.

Numerics: every product runs at ``Precision.HIGHEST`` in float32, so the
reference stands above the bfloat16 the configuration serves in.  With
``dtype="float8_e4m3fn"`` it becomes the control: every stored value,
weight and product operand is rounded to that type (saturating at its
largest finite value) before use, the next precision below bfloat16.

A fold may be computed padded to a longer length (the served bucket), so
that one compiled program serves every length of a bucket: padded keys get
an additive -1e9 in every attention and padded pairs a zero in the
triangular products, which leaves the real positions exactly as the
unpadded fold computes them.

Memory: blocks run one at a time through one jitted block function, and the
triangular attention runs over row chunks, so a 1,024-residue fold fits one
16 GB chip in float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
LN_EPS = 1e-5
ATTN_ROWS = 64              # rows of the triangular attention per chunk
MASKED = -1e9               # additive logit of a padded key: exp() is 0.0


def _rounder(dtype: str):
    """Value rounding of the precision under test: identity for float32,
    a saturating round trip through ``dtype`` otherwise."""
    if dtype == "float32":
        return lambda x: x
    dt = jnp.dtype(dtype)
    big = float(jnp.finfo(dt).max)
    return lambda x: jnp.clip(x, -big, big).astype(dt).astype(F32)


class _Num:
    """The arithmetic one reference run uses: ``r`` rounds a stored value,
    ``mm`` is an einsum of rounded operands accumulated in float32."""

    def __init__(self, dtype: str):
        self.r = _rounder(dtype)

    def mm(self, eq, a, b):
        return self.r(jnp.einsum(eq, self.r(a), self.r(b), precision=HI,
                                 preferred_element_type=F32))

    def dense(self, p, x):
        y = self.mm("...i,io->...o", x, p["w"].astype(F32))
        return y if "b" not in p else self.r(y + p["b"].astype(F32))

    def ln(self, p, x):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        y = (x - mu) / jnp.sqrt(var + LN_EPS)
        return self.r(y * p["g"].astype(F32) + p["b"].astype(F32))


# --------------------------------------------------------------------------
# AAQ
# --------------------------------------------------------------------------
def fake_quant(x, bits: int, k: int):
    """Token-wise AAQ of ``x`` over its last axis, dequantized: the ``k``
    entries of largest magnitude (ties to the lower index) pass unchanged;
    the rest are rounded to ``bits``-bit integers on the scale
    max|inlier| / (2**(bits-1) - 1)."""
    qmax = 2.0 ** (bits - 1) - 1.0
    h = x.shape[-1]
    col = jnp.arange(h)
    keep = jnp.zeros(x.shape, bool)
    a = jnp.abs(x)
    for _ in range(k):
        pick = jnp.argmax(a, axis=-1)[..., None] == col   # first max index
        keep = keep | pick
        a = jnp.where(pick, -1.0, a)
    inl = jnp.where(keep, 0.0, x)
    scale = jnp.maximum(jnp.max(jnp.abs(inl), axis=-1, keepdims=True) / qmax,
                        1e-12)
    q = jnp.clip(jnp.round(inl / scale), -qmax, qmax)
    return jnp.where(keep, x, q * scale)


class _Scheme:
    """Where the scheme touches the pair track: ``act`` at a storage site,
    ``linear`` for a pair-track product.  ``groups`` maps a site suffix to
    (bits, outliers); None is the unquantized baseline."""

    def __init__(self, num: _Num, groups: dict | None):
        self.num = num
        self.groups = groups

    def _fq(self, x, site: str):
        bits, k = self.groups[site.rsplit(".", 1)[1]]
        return self.num.r(fake_quant(x, bits, k))

    def act(self, x, site: str):
        return x if self.groups is None else self._fq(x, site)

    def linear(self, p, x, site: str):
        return self.num.dense(p, x if self.groups is None
                              else self._fq(x, site))


def scheme_groups(cfg: dict):
    """The configuration's AAQ table as {site suffix: (bits, outliers)}, or
    None for an unquantized scheme."""
    q = cfg.get("aaq")
    if q is None:
        return None
    return {site: tuple(q["groups"][g]) for site, g in q["sites"].items()}


# --------------------------------------------------------------------------
# the folding block
# --------------------------------------------------------------------------
def _softmax_attend(num, q, k, v, bias):
    """q (..., S, H, D), k/v (..., T, H, D), bias broadcastable to
    (..., H, S, T): softmax(q.k / sqrt(D) + bias) v, in float32."""
    logits = jnp.einsum("...shd,...thd->...hst", num.r(q), num.r(k),
                        precision=HI, preferred_element_type=F32)
    logits = logits / math.sqrt(q.shape[-1]) + bias
    probs = jax.nn.softmax(logits, axis=-1)
    return num.r(jnp.einsum("...hst,...thd->...shd", num.r(probs), num.r(v),
                            precision=HI, preferred_element_type=F32))


def seq_attention(num, p, s, z, heads: int, keys):
    n, hm = s.shape
    sl = num.ln(p["ln"], s)
    q, k, v = jnp.split(num.dense(p["qkv"], sl), 3, axis=-1)
    shape = (n, heads, hm // heads)
    bias = num.dense(p["pair_bias"], num.ln(p["pair_bias_ln"], z))  # (N,N,H)
    o = _softmax_attend(num, q.reshape(shape), k.reshape(shape),
                        v.reshape(shape),
                        jnp.transpose(bias, (2, 0, 1)) + keys)
    g = jax.nn.sigmoid(num.dense(p["gate"], sl))
    return num.dense(p["out"], num.r(g * o.reshape(n, hm)))


def seq_transition(num, p, s):
    h = jax.nn.relu(num.dense(p["up"], num.ln(p["ln"], s)))
    return num.dense(p["down"], h)


def outer_product_mean(num, p, s):
    sl = num.ln(p["ln"], s)
    a, b = num.dense(p["a"], sl), num.dense(p["b"], sl)
    n, c = a.shape
    outer = num.r(a[:, None, :, None] * b[None, :, None, :])
    return num.dense(p["out"], outer.reshape(n, n, c * c))


def tri_mul(sch, p, z, outgoing: bool, sc: str, pairs):
    num = sch.num
    z = sch.act(z, f"{sc}.pre_ln")
    zl = sch.act(num.ln(p["ln_in"], z), f"{sc}.post_ln")

    def gated(proj, gate):
        g = jax.nn.sigmoid(sch.linear(p[gate], zl, f"{sc}.gate"))
        return sch.act(num.r(g * sch.linear(p[proj], zl, f"{sc}.post_ln")),
                       f"{sc}.ab") * pairs

    a, b = gated("a_proj", "a_gate"), gated("b_proj", "b_gate")
    eq = "ikc,jkc->ijc" if outgoing else "kic,kjc->ijc"
    x = sch.act(num.mm(eq, a, b), f"{sc}.prod_pre_ln")
    xl = sch.act(num.ln(p["ln_out"], x), f"{sc}.post_ln")
    g = jax.nn.sigmoid(sch.linear(p["out_gate"], zl, f"{sc}.gate"))
    return sch.act(num.r(g * sch.linear(p["out"], xl, f"{sc}.post_ln")),
                   f"{sc}.out")


def tri_attention(sch, p, z, starting: bool, sc: str, heads: int, keys):
    num = sch.num
    if not starting:
        z = jnp.swapaxes(z, 0, 1)
    n, _, hz = z.shape
    dh = hz // heads
    z = sch.act(z, f"{sc}.pre_ln")
    zl = sch.act(num.ln(p["ln"], z), f"{sc}.post_ln")
    q, k, v = jnp.split(sch.linear(p["qkv"], zl, f"{sc}.qkv_in"), 3, axis=-1)
    bias = jnp.transpose(sch.linear(p["bias"], zl, f"{sc}.post_ln"),
                         (2, 0, 1)) + keys                # (H, j, k)
    rows = math.gcd(n, ATTN_ROWS)

    def chunk(qkv):                   # one slab of rows i: (rows, N, H, D)
        qc, kc, vc = (t.reshape(rows, n, heads, dh) for t in qkv)
        return _softmax_attend(num, qc, kc, vc, bias[None])

    slabs = tuple(t.reshape(n // rows, rows, n, hz) for t in (q, k, v))
    o = jax.lax.map(chunk, slabs).reshape(n, n, hz)
    o = sch.act(o, f"{sc}.av")
    g = jax.nn.sigmoid(sch.linear(p["gate"], zl, f"{sc}.gate"))
    out = sch.linear(p["out"], num.r(g * o), f"{sc}.proj_in")
    return out if starting else jnp.swapaxes(out, 0, 1)


def pair_transition(sch, p, z, sc: str = "pair_trans"):
    num = sch.num
    z = sch.act(z, f"{sc}.pre_ln")
    zl = sch.act(num.ln(p["ln"], z), f"{sc}.post_ln")
    h = sch.act(jax.nn.relu(sch.linear(p["up"], zl, f"{sc}.post_ln")),
                f"{sc}.proj_in")
    return sch.linear(p["down"], h, f"{sc}.proj_in")


def block(sch, cfg: dict, p, s, z, mask):
    """One folding block; ``mask`` (N,) marks the real residues."""
    num = sch.num
    keys = jnp.where(mask, 0.0, MASKED)                   # over the last axis
    pairs = (mask[:, None] & mask[None, :])[..., None].astype(F32)
    s = num.r(s + seq_attention(num, p["seq_attn"], s, z, cfg["seq_heads"],
                                keys))
    s = num.r(s + seq_transition(num, p["seq_trans"], s))
    z = num.r(z + outer_product_mean(num, p["opm"], s))
    z = num.r(z + tri_mul(sch, p["tri_mul_out"], z, True, "tri_mul_out",
                          pairs))
    z = num.r(z + tri_mul(sch, p["tri_mul_in"], z, False, "tri_mul_in",
                          pairs))
    z = num.r(z + tri_attention(sch, p["tri_attn_start"], z, True,
                                "tri_attn_start", cfg["pair_heads"], keys))
    z = num.r(z + tri_attention(sch, p["tri_attn_end"], z, False,
                                "tri_attn_end", cfg["pair_heads"], keys))
    return s, num.r(z + pair_transition(sch, p["pair_trans"], z))


# --------------------------------------------------------------------------
# embedding, structure module, heads
# --------------------------------------------------------------------------
def embed(num, cfg: dict, params, aatype):
    s0 = num.r(params["aa_embed"]["e"].astype(F32)[aatype])
    li, ri = num.dense(params["left"], s0), num.dense(params["right"], s0)
    n = aatype.shape[0]
    half = cfg["relpos_bins"] // 2
    rel = jnp.clip(jnp.arange(n)[:, None] - jnp.arange(n)[None, :],
                   -half, half) + half
    z0 = li[:, None, :] + ri[None, :, :] + params["relpos"]["e"].astype(F32)[rel]
    return s0, num.r(z0)


def structure(num, p, s, z, n_iter: int, mask):
    n, hm = s.shape
    heads = p["pair_bias"]["w"].shape[-1]
    shape = (n, heads, hm // heads)
    bias = jnp.transpose(num.dense(p["pair_bias"], num.ln(p["ln_z"], z)),
                         (2, 0, 1))                       # (H, N, N)
    bias = bias + jnp.where(mask, 0.0, MASKED)
    w = jax.nn.softplus(p["dist_w"].astype(F32))[:, None, None]
    t = jnp.zeros((n, 3), F32)
    for _ in range(n_iter):
        sl = num.ln(p["ln_s"], s)
        q, k, v = jnp.split(num.dense(p["qkv"], sl), 3, axis=-1)
        d2 = jnp.sum(jnp.square(t[:, None] - t[None, :]), axis=-1)
        o = _softmax_attend(num, q.reshape(shape), k.reshape(shape),
                            v.reshape(shape), bias - w * d2[None])
        s = num.r(s + num.dense(p["out"], o.reshape(n, hm)))
        m = p["trans_mlp"]
        s = num.r(s + num.dense(m["down"], jax.nn.relu(
            num.dense(m["up"], num.ln(m["ln"], s)))))
        t = t + num.dense(p["coord"], num.ln(p["coord_ln"], s))
    return t


class Reference:
    """``Reference(cfg, dtype)(params, aatype, pad_to)`` -> (coords (N, 3),
    distogram (N, N, bins)), float32 numpy, of the N residues of
    ``aatype``.  One jitted program per piece and padded length; blocks
    run one at a time."""

    def __init__(self, cfg: dict, dtype: str = "float32"):
        self.cfg = cfg
        num = _Num(dtype)
        sch = _Scheme(num, scheme_groups(cfg))
        self._embed = jax.jit(functools.partial(embed, num, cfg))
        self._ln = jax.jit(num.ln)
        self._block = jax.jit(lambda trunk, i, s, z, m: block(
            sch, cfg, jax.tree.map(lambda a: a[i], trunk), s, z, m))

        def head(params, s, z, m):
            coords = structure(num, params["structure"], s, z,
                               cfg["ipa_iters"], m)
            zsym = 0.5 * (z + jnp.swapaxes(z, 0, 1))
            return coords, num.dense(params["distogram"], num.r(zsym))

        self._head = jax.jit(head)

    def __call__(self, params, aatype, pad_to: int | None = None):
        n = len(aatype)
        pad = (pad_to or n) - n
        mask = jnp.arange(n + pad) < n
        aatype = jnp.pad(jnp.asarray(aatype, jnp.int32), (0, pad))
        s0, z0 = self._embed(params, aatype)
        s, z = s0, z0
        for r in range(self.cfg["recycles"]):
            s_in, z_in = s0, z0
            if r:
                s_in = s0 + self._ln(params["recycle_s_ln"], s)
                z_in = z0 + self._ln(params["recycle_z_ln"], z)
            s, z = s_in, z_in
            for i in range(self.cfg["blocks"]):
                s, z = self._block(params["trunk"], jnp.int32(i), s, z, mask)
        coords, disto = self._head(params, s, z, mask)
        return np.asarray(coords[:n]), np.asarray(disto[:n, :n])
