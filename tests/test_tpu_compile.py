"""The Pallas kernels compile for a TPU v5e at esmfold_ppm widths.

Each case lowers one kernel with ``interpret=False`` against a described
(not attached) v5e chip, so a block shape or an op that Mosaic refuses
fails here, on the CPU, before any chip run.  Nothing runs; the topology
is described inside a fixture so that importing this file never loads the
TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.aaq_matmul.aaq_matmul import aaq_matmul_pallas
from repro.kernels.aaq_quant.aaq_quant import aaq_quantize_pallas
from repro.kernels.flash_attention.flash_attention import flash_mha_pallas

T, HZ, D_OUT = 65536, 128, 512          # pair-stack tokens at N=256, Hz, 4*Hz
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _flash(sharding, n, heads, dh, with_bias):
    """Triangular (rows as batch, bias + kv length) or sequence attention."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    b = n if with_bias else 1
    qkv = [s((b, n, heads, dh), BF16) for _ in range(3)]
    if with_bias:
        return (functools.partial(flash_mha_pallas, interpret=False),
                (*qkv, s((1, heads, n, n), BF16), s((b,), jnp.int32)))
    return (lambda q, k, v, bias: flash_mha_pallas(q, k, v, bias,
                                                   interpret=False),
            (*qkv, s((1, heads, n, n), jnp.float32)))


def _quant(sharding, bits, k):
    return (functools.partial(aaq_quantize_pallas, bits=bits, k_outliers=k,
                              interpret=False),
            (jax.ShapeDtypeStruct((T, HZ), BF16, sharding=sharding),))


def _matmul(sharding, bits, k):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    hp = HZ // 2 if bits == 4 else HZ
    kk = max(k, 1)
    return (functools.partial(aaq_matmul_pallas, bits=bits, out_dtype=BF16,
                              interpret=False),
            (s((T, hp), jnp.int8), s((T, 1), jnp.float32), s((T, kk), BF16),
             s((T, kk), jnp.int32), s((HZ, D_OUT), BF16)))


CASES = {
    "flash-tri-N256-H4-D32-bias": lambda sh: _flash(sh, 256, 4, 32, True),
    "flash-seq-N512-H16-D64": lambda sh: _flash(sh, 512, 16, 64, False),
    "quant-groupA-int8-k4": lambda sh: _quant(sh, 8, 4),
    "quant-groupB-int4-k4": lambda sh: _quant(sh, 4, 4),
    "quant-groupC-int4-k0": lambda sh: _quant(sh, 4, 0),
    "matmul-groupA-int8-k4": lambda sh: _matmul(sh, 8, 4),
    "matmul-groupB-int4-k4": lambda sh: _matmul(sh, 4, 4),
    "matmul-groupC-int4-k0": lambda sh: _matmul(sh, 4, 0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    fn, args = CASES[case](one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
