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


def _flash(sharding, n, heads, dh, with_bias, rows=None):
    """Triangular (``rows`` rows of one protein as batch, default ``n``; a
    bf16 bias + kv length) or sequence attention (one protein, f32 bias)."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    b = (rows or n) if with_bias else 1
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
    # the served shapes: a long fold's chunk of 256 rows at N=1,024, and
    # its sequence attention (32 heads of 32, an f32 bias per protein)
    "flash-tri-chunk256-N1024-H4-D32-bias":
        lambda sh: _flash(sh, 1024, 4, 32, True, rows=256),
    "flash-seq-N1024-H32-D32": lambda sh: _flash(sh, 1024, 32, 32, False),
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


def test_flash_custom_call_reads_as_the_roofline_counts_it(one_chip,
                                                           no_persistent_cache):
    """The compiled long-chunk call, as the device trace names it, is
    attributed to ``flash_mha_pallas`` and its work read at the shapes it
    ran with: 4 B H S T D operations and each operand's bytes once.  A
    layout change that the roofline's parser would misread fails here."""
    from jax._src.lib import xla_client

    from bench.ops.kernels import flash_call
    from bench.trace import kernel_of

    rows, n, heads, dh = 256, 1024, 4, 32
    fn, args = _flash(one_chip, n, heads, dh, True, rows=rows)
    compiled = jax.jit(fn).lower(*args).compile()
    opts = xla_client._xla.HloPrintOptions.short_parsable()
    opts.print_operand_shape = True
    opts.print_percent = True
    text = "\n".join(m.to_string(opts)
                     for m in compiled.runtime_executable().hlo_modules())
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line and " = " in line]
    assert len(calls) == 1
    assert kernel_of(calls[0].partition(" = ")[0]) == "flash_mha_pallas"
    call = flash_call(calls[0])
    assert call.ops == 4 * rows * heads * n * n * dh
    qkvo = 4 * rows * n * heads * dh * 2
    assert call.bytes == qkvo + heads * n * n * 2 + rows * 4
