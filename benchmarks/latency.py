"""Paper Fig. 14 analogue: folding-block latency scaling with sequence
length, CPU-measured (relative scaling is the signal here — absolute TPU
latency comes from the §Roofline terms), plus kernel microbenches.
"""
from __future__ import annotations

import jax

from benchmarks.common import emit, time_fn
from repro.models.ppm import init_ppm, ppm_forward
from repro.models.ppm.trunk import PPMConfig

CFG = PPMConfig(blocks=1, hm=128, hz=64, seq_heads=4, pair_heads=4,
                tri_hidden=64, vocab=23, recycles=1, ipa_iters=1,
                dtype="float32")


def main():
    params = init_ppm(jax.random.PRNGKey(0), CFG)
    prev = None
    for ns in (32, 64, 128):
        aatype = jax.random.randint(jax.random.PRNGKey(1), (1, ns), 0, 20)
        f = jax.jit(lambda p, a: ppm_forward(p, a, CFG)["coords"])
        us = time_fn(f, params, aatype)
        growth = f"growth={us / prev:.2f}x" if prev else ""
        emit(f"latency/ppm_block/ns{ns}", us, growth)
        prev = us

    # kernel microbenches (interpret mode off-TPU: correctness-path timing)
    from repro.kernels import dispatch
    from repro.kernels.aaq_quant.ops import aaq_quantize
    interp = dispatch.interpret_mode()
    from repro.kernels.aaq_quant.ref import aaq_quantize_ref
    x = jax.random.normal(jax.random.PRNGKey(0), (4096, 128))
    us_k = time_fn(lambda a: aaq_quantize(a, 8, 4, use_kernel=True,
                                          interpret=interp).inliers, x)
    us_r = time_fn(lambda a: aaq_quantize_ref(a, 8, 4)[0], x)
    emit("kernel/aaq_quant_interp", us_k, f"ref_jnp={us_r:.0f}us")

    from repro.kernels.flash_attention.flash_attention import flash_mha_pallas
    from repro.kernels.flash_attention.ref import mha_ref
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 4, 32))
    us_f = time_fn(lambda a: flash_mha_pallas(a, a, a, causal=True,
                                                      interpret=interp), q)
    us_m = time_fn(lambda a: mha_ref(a, a, a, causal=True), q)
    emit("kernel/flash_attn_interp", us_f, f"ref_jnp={us_m:.0f}us")

    # same call routed through the dispatch layer, both backends
    us_dp = time_fn(
        lambda a: dispatch.attention(a, a, a, causal=True, backend="pallas"), q)
    us_dr = time_fn(
        lambda a: dispatch.attention(a, a, a, causal=True, backend="ref"), q)
    emit("kernel/dispatch_attn", us_dp,
         f"ref={us_dr:.0f}us backend={dispatch.describe('pallas')}")


if __name__ == "__main__":
    main()
