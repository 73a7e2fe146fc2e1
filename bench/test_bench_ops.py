"""Operation and byte counts of bench/ops at small shapes: against counts
by hand, and the fold's FLOPs against the products the plain reference
actually traces."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.ops import kernels, ppm
from bench.reference import ppm as ref

TINY = dict(blocks=1, hm=8, hz=4, seq_heads=2, pair_heads=2, tri_hidden=4,
            transition_factor=2, vocab=23, relpos_bins=65, recycles=1,
            distogram_bins=3, ipa_iters=1, dtype="bfloat16",
            aaq={"groups": {"A": [8, 4], "B": [4, 4], "C": [4, 0]},
                 "sites": {"pre_ln": "A", "post_ln": "B", "qkv_in": "B",
                           "gate": "C", "ab": "C", "prod_pre_ln": "C",
                           "out": "C", "av": "C", "proj_in": "C"}})


def test_block_macs_by_hand():
    # n = 2, t = 4 pair tokens; each line is the op's products written out
    got = ppm.block_macs(TINY, 2)
    assert got == {
        "tri_mul": 2 * (4 * 4 * 4 * 4 + 2 * 4 * 4 + 4 * 4 * 4 + 4 * 4 * 4),
        "tri_attn": 2 * (3 * 4 * 4 * 4 + 4 * 4 * 2 + 2 * 2 * 4 * 4
                         + 2 * 4 * 4 * 4),
        "pair_trans": 2 * 4 * 4 * 2 * 4,
        "opm": 2 * 2 * 8 * 32 + 4 * 32 * 32 + 4 * 1024 * 4,
        "seq_attn": 4 * 4 * 2 + 3 * 2 * 8 * 8 + 2 * 4 * 8 + 2 * 2 * 8 * 8,
        "seq_trans": 2 * 2 * 8 * 2 * 8,
    }


def _macs(jaxpr) -> int:
    """Multiply-accumulates of every dot_general in a jaxpr, sub-programs
    and loops included, plus the outer product's multiplies (the one
    elementwise product the model counts as work)."""
    total = 0
    for eqn in jaxpr.eqns:
        p = eqn.primitive.name
        if p == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            k = int(np.prod([eqn.invars[0].aval.shape[i] for i in lc]))
            total += k * int(np.prod(eqn.outvars[0].aval.shape))
        elif p == "mul" and eqn.outvars[0].aval.ndim == 4:
            total += int(np.prod(eqn.outvars[0].aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            inner = _macs(sub)
            total += inner * (eqn.params.get("length", 1)
                              if p == "scan" else 1)
    return total


@pytest.mark.parametrize("n", [5, 8])
def test_fold_flops_match_the_reference(n):
    cfg = dict(TINY, aaq=None)
    from bench.weights import make_params
    params = jax.eval_shape(lambda: make_params(cfg, 0))
    num = ref._Num("float32")
    sch = ref._Scheme(num, None)
    f32 = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), t)
    trunk = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:],
                                                        jnp.float32),
                         params["trunk"])
    s = jax.ShapeDtypeStruct((n, cfg["hm"]), jnp.float32)
    z = jax.ShapeDtypeStruct((n, n, cfg["hz"]), jnp.float32)
    aat = jax.ShapeDtypeStruct((n,), jnp.int32)
    embed = jax.make_jaxpr(functools.partial(ref.embed, num, cfg))(
        f32(params), aat)
    mask = jax.ShapeDtypeStruct((n,), jnp.bool_)
    block = jax.make_jaxpr(functools.partial(ref.block, sch, cfg))(
        trunk, s, z, mask)
    ipa = jax.make_jaxpr(lambda p, s, z, m: ref.structure(
        num, p, s, z, cfg["ipa_iters"], m))(f32(params["structure"]), s, z,
                                            mask)
    head = jax.make_jaxpr(lambda p, z: num.dense(p, z))(
        f32(params["distogram"]), z)
    traced = (_macs(embed.jaxpr) + cfg["blocks"] * _macs(block.jaxpr)
              + _macs(ipa.jaxpr) + _macs(head.jaxpr))
    assert ppm.fold_flops(cfg, n) == 2 * traced


FLASH = ("%flash_mha_pallas.26 = bf16[2,16,128,64]{3,2,1,0:T(8,128)(2,1)S(1)} "
         "custom-call(bf16[2,16,128,64]{3,2,1,0:T(8,128)(2,1)S(1)} %bitcast.1, "
         "bf16[2,16,128,64]{3,2,1,0:T(8,128)(2,1)S(1)} %bitcast.2, "
         "bf16[2,16,128,64]{3,2,1,0:T(8,128)(2,1)S(1)} %fusion.10, "
         "f32[2,16,128,128]{3,2,1,0:T(8,128)S(1)} %fusion.2), "
         "custom_call_target=\"tpu_custom_call\", operand_layout_constraints="
         "{bf16[2,16,128,64]{3,2,1,0}, f32[2,16,128,128]{3,2,1,0}}")
QUANT = ("%aaq_quantize_pallas.93 = (s8[32768,64]{1,0:T(8,128)(4,1)S(1)}, "
         "f32[32768,1]{1,0:T(8,128)S(1)}, bf16[32768,1]{1,0:T(8,128)(2,1)}, "
         "s32[32768,1]{1,0:T(8,128)}) custom-call(bf16[32768,128]"
         "{1,0:T(8,128)(2,1)S(1)} %bitcast.1148), custom_call_target="
         "\"tpu_custom_call\", operand_layout_constraints={bf16[32768,128]{1,0}}")


def test_flash_call_by_hand():
    c = kernels.flash_call(FLASH)
    # q.k and p.v of 2 x 16 heads, 128 queries over 128 keys of width 64
    assert c.ops == 4 * 2 * 16 * 128 * 128 * 64
    # q, k, v and the output in bf16, the float32 bias; nothing twice
    assert c.bytes == 4 * (2 * 16 * 128 * 64 * 2) + 2 * 16 * 128 * 128 * 4
    assert c.bound(197e12, 819e9) == "memory"
    assert c.least_s(197e12, 819e9) == pytest.approx(c.bytes / 819e9)


def test_quantize_call_by_hand():
    c = kernels.quantize_call(QUANT)
    t = 32768
    # read 128 bf16 a token; write 64 packed bytes, an f32 scale and one
    # (bf16, int32) outlier slot a token
    assert c.bytes == t * (128 * 2 + 64 + 4 + 2 + 4)
    assert c.ops == t * 128 * (1 + 3)


def test_roofline_share_reads_least_over_spent():
    from types import SimpleNamespace
    peak = {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9}
    least = kernels.flash_call(FLASH).least_s(197e12, 819e9)
    trace = SimpleNamespace(kernel_s={"flash_mha_pallas": 12 * least},
                            kernel_calls={"flash_mha_pallas": {FLASH: 3}})
    run = SimpleNamespace(trace=trace, peak=peak)
    assert kernels.roofline_share(run, "flash_mha_pallas") == \
        pytest.approx(25.0)
    # a kernel that ``KERNELS`` does not list, with its own work function
    trace.kernel_s["new_kernel"] = 4 * least
    trace.kernel_calls["new_kernel"] = {"text": 1}
    assert kernels.roofline_share(
        run, "new_kernel", work=lambda text: kernels.flash_call(FLASH)) == \
        pytest.approx(25.0)
    # no call of the kernel in the trace, or no trace: nothing to read
    assert kernels.roofline_share(run, "aaq_quantize_pallas") is None
    assert kernels.roofline_share(SimpleNamespace(trace=None, peak=peak),
                                  "flash_mha_pallas") is None
