"""Full Protein Structure Prediction Model (paper Fig. 2a).

Input Embedding -> Protein Folding Blocks (trunk) -> Structure Module, with
recycling.  The upstream protein language model (ESM-2 in ESMFold) is the
Input-Embedding *stub*: a learned amino-acid embedding + relative-position
pair embedding — the paper's contribution (and its latency/memory bottleneck)
is entirely inside the folding block, which is implemented in full.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.schemes import FP16Baseline, QuantScheme
from repro.models import common as cm
from repro.models.ppm import structure as st
from repro.models.ppm import trunk as tk
from repro.models.ppm.trunk import PPMConfig


def init_ppm(key, cfg: PPMConfig) -> cm.Params:
    k1, k2, k3, k4, k5, k6, k7 = jax.random.split(key, 7)
    dt = cfg.np_dtype
    return {
        "aa_embed": cm.embed_init(k1, cfg.vocab, cfg.hm, dt),
        "left": cm.dense_init(k2, cfg.hm, cfg.hz, dtype=dt),
        "right": cm.dense_init(k3, cfg.hm, cfg.hz, dtype=dt),
        "relpos": cm.embed_init(k4, cfg.relpos_bins, cfg.hz, dt),
        "recycle_s_ln": cm.ln_init(cfg.hm, dt),
        "recycle_z_ln": cm.ln_init(cfg.hz, dt),
        "trunk": tk.init_trunk(k5, cfg),
        "structure": st.init_structure(k6, cfg),
        "distogram": cm.dense_init(k7, cfg.hz, cfg.distogram_bins, bias=True, dtype=dt),
    }


def input_embedding(p, aatype: jax.Array, cfg: PPMConfig):
    """aatype (B,N) int32 -> s0 (B,N,Hm), z0 (B,N,N,Hz)."""
    s0 = cm.embed(p["aa_embed"], aatype)
    li = cm.dense(p["left"], s0)
    ri = cm.dense(p["right"], s0)
    z0 = li[:, :, None, :] + ri[:, None, :, :]
    n = aatype.shape[-1]
    rel = jnp.clip(jnp.arange(n)[:, None] - jnp.arange(n)[None, :],
                   -(cfg.relpos_bins // 2), cfg.relpos_bins // 2) + cfg.relpos_bins // 2
    z0 = z0 + cm.embed(p["relpos"], rel)[None]
    return s0.astype(cfg.np_dtype), z0.astype(cfg.np_dtype)


def ppm_forward(params, aatype: jax.Array, cfg: PPMConfig,
                scheme: QuantScheme | None = None, *, mask: jax.Array | None = None,
                remat: bool = False, chunk_size: int | None = None):
    """Full forward pass. Returns dict with coords, distogram, s, z.

    ``mask`` (B, N) bool marks real tokens when ``aatype`` is padded to a
    serving bucket; ``None`` is the legacy unmasked path.  Masking is
    non-rescaling (see trunk helpers), so coords/s at real positions are
    bitwise identical to an unpadded forward of the same sequence.

    ``chunk_size`` routes the trunk through the row-chunked pair stack
    (repro.models.ppm.chunking) — the long-fold path the memory planner
    prices; None/0 is the unchunked path.
    """
    scheme = scheme or FP16Baseline()
    if mask is not None:
        mask = mask.astype(bool)
    s0, z0 = input_embedding(params, aatype, cfg)
    s, z = s0, z0
    for r in range(cfg.recycles):
        s_in = s0 + (cm.layernorm(params["recycle_s_ln"], s) if r else 0.0)
        z_in = z0 + (cm.layernorm(params["recycle_z_ln"], z) if r else 0.0)
        s, z = tk.trunk_apply(params["trunk"], s_in, z_in, cfg, scheme,
                              remat=remat, mask=mask, chunk_size=chunk_size)
    with jax.named_scope("structure"):     # its flash attention calls
        coords, s_final = st.structure_apply(params["structure"], s, z,
                                             n_iter=cfg.ipa_iters, mask=mask)
    zsym = 0.5 * (z + jnp.swapaxes(z, 1, 2))
    distogram = cm.dense(params["distogram"], zsym)
    return {"coords": coords, "distogram": distogram, "s": s_final, "z": z}


# --------------------------------------------------------------------------
# activation inventory — drives the footprint benches (paper Table 1, Fig 16b)
# --------------------------------------------------------------------------
def pair_activation_inventory(cfg: PPMConfig, ns: int, batch: int = 1):
    """Every Pair-dataflow activation one block stores, as (site, shape).

    This is the denominator of the paper's Table-1 accounting: the tensors a
    scheme must hold in memory per block (score tensors excluded — they are
    the *peak* story, handled by token-wise MHA / flash attention).
    """
    hz, th, f, h = cfg.hz, cfg.tri_hidden, cfg.transition_factor, cfg.pair_heads
    inv: list[tuple[str, tuple[int, ...]]] = []
    for sc in ("tri_mul_out", "tri_mul_in"):
        inv += [(f"{sc}.pre_ln", (batch, ns, ns, hz)),
                (f"{sc}.post_ln", (batch, ns, ns, hz)),
                (f"{sc}.ab", (batch, ns, ns, th)),
                (f"{sc}.ab", (batch, ns, ns, th)),
                (f"{sc}.prod_pre_ln", (batch, ns, ns, th)),
                (f"{sc}.out", (batch, ns, ns, hz))]
    for sc in ("tri_attn_start", "tri_attn_end"):
        inv += [(f"{sc}.pre_ln", (batch, ns, ns, hz)),
                (f"{sc}.post_ln", (batch, ns, ns, hz)),
                (f"{sc}.qkv_in", (batch, ns, ns, 3 * hz)),
                (f"{sc}.av", (batch, ns, ns, hz)),
                (f"{sc}.proj_in", (batch, ns, ns, hz))]
    inv += [("pair_trans.pre_ln", (batch, ns, ns, hz)),
            ("pair_trans.post_ln", (batch, ns, ns, hz)),
            ("pair_trans.proj_in", (batch, ns, ns, f * hz))]
    return inv


def score_tensor_shape(cfg: PPMConfig, ns: int, batch: int = 1):
    """The cubic triangular-attention score tensor (per tri-attn op)."""
    return (batch, cfg.pair_heads, ns, ns, ns)
