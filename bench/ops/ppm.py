"""Matrix-multiply FLOPs of one fold at its real length.

Counts every product of the fold model as 2 x multiply-accumulates: the
pair track's (triangular multiplication and attention, pair transition,
outer-product mean; the MAC counts of the pair dataflow follow
``benchmarks/compute_cost.py:block_macs``), the sequence track's, the input
embedding's, the structure module's and the distogram head's.  Layer norms,
softmax and quantization are not counted.  The count is FP-equivalent: a
quantized product counts as the product it stands for, so it is the same
work under every scheme.
"""
from __future__ import annotations


def block_macs(cfg: dict, n: int) -> dict[str, int]:
    """MACs of one folding block at length ``n``, by op."""
    hm, hz, th = cfg["hm"], cfg["hz"], cfg["tri_hidden"]
    f, ph, sh = cfg["transition_factor"], cfg["pair_heads"], cfg["seq_heads"]
    t = n * n                                   # pair tokens
    tri_mul = (4 * t * hz * th                  # a/b projections and gates
               + n * t * th                     # the k-contraction
               + t * th * hz                    # output projection
               + t * hz * hz)                   # output gate
    tri_attn = (3 * t * hz * hz                 # q, k, v
                + t * hz * ph                   # bias
                + 2 * n * t * hz                # q.k and p.v
                + 2 * t * hz * hz)              # gate, output
    return {
        "seq_attn": (t * hz * sh + 3 * n * hm * hm + 2 * t * hm
                     + 2 * n * hm * hm),
        "seq_trans": 2 * n * hm * f * hm,
        "opm": 2 * n * hm * 32 + t * 32 * 32 + t * 32 * 32 * hz,
        "tri_mul": 2 * tri_mul,
        "tri_attn": 2 * tri_attn,
        "pair_trans": 2 * t * hz * f * hz,
    }


def fold_flops(cfg: dict, n: int) -> int:
    """FLOPs of one whole fold of length ``n``: embedding, ``recycles`` x
    ``blocks`` folding blocks, structure module and distogram head."""
    hm, hz, sh = cfg["hm"], cfg["hz"], cfg["seq_heads"]
    t = n * n
    embed = 2 * n * hm * hz
    trunk = cfg["recycles"] * cfg["blocks"] * sum(block_macs(cfg, n).values())
    ipa = (t * hz * sh + cfg["ipa_iters"] * (
        3 * n * hm * hm + 2 * t * hm + n * hm * hm + 4 * n * hm * hm
        + 3 * n * hm))
    head = t * hz * cfg["distogram_bins"]
    return 2 * (embed + trunk + ipa + head)
