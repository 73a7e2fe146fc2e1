"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dequantize, qmatmul, quantize
from repro.core.qtensor import unpack_int4
from repro.kernels.aaq_matmul.ops import aaq_linear
from repro.kernels.aaq_matmul.ref import aaq_matmul_ref
from repro.kernels.aaq_quant.ops import aaq_quantize
from repro.kernels.flash_attention.flash_attention import flash_mha_pallas
from repro.kernels.flash_attention.ref import mha_chunked, mha_ref


@pytest.mark.parametrize("t,h", [(100, 128), (256, 128), (64, 256), (8, 64),
                                 (257, 128)])
@pytest.mark.parametrize("bits,k", [(8, 4), (4, 4), (4, 0), (8, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_aaq_quant_kernel_vs_ref(t, h, bits, k, dtype):
    x = (jax.random.normal(jax.random.PRNGKey(0), (t, h)) * 2).astype(dtype)
    x = x.at[0, 5].set(50.0)
    qk = aaq_quantize(x, bits, k, use_kernel=True, interpret=True)
    qr = quantize(x, bits, k)
    ik = unpack_int4(qk.inliers) if bits == 4 else qk.inliers
    ir = unpack_int4(qr.inliers) if bits == 4 else qr.inliers
    # 1-LSB tolerance: rounding ties may resolve differently across paths
    assert int(jnp.max(jnp.abs(ik.astype(jnp.int32) - ir.astype(jnp.int32)))) <= 1
    np.testing.assert_allclose(np.asarray(qk.scales), np.asarray(qr.scales),
                               rtol=1e-6)
    sc = float(jnp.max(qk.scales))
    np.testing.assert_allclose(
        np.asarray(dequantize(qk), np.float32),
        np.asarray(dequantize(qr), np.float32), atol=1.01 * sc)


@pytest.mark.parametrize("t,h,d", [(64, 128, 96), (256, 128, 64), (33, 64, 128)])
@pytest.mark.parametrize("bits,k", [(8, 4), (4, 4), (4, 0)])
def test_aaq_matmul_kernel_vs_ref(t, h, d, bits, k):
    x = jax.random.normal(jax.random.PRNGKey(0), (t, h)) * 2
    x = x.at[3, 7].set(-60.0)
    w = jax.random.normal(jax.random.PRNGKey(1), (h, d))
    qt = quantize(x, bits, k)
    yk = aaq_linear(x, w, bits=bits, k_outliers=k, block_t=64, block_d=64,
                    interpret=True)
    yr = qmatmul(qt, w)
    sc = float(jnp.max(qt.scales))
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr),
                               rtol=2e-2, atol=2 * sc * np.sqrt(h))


def test_aaq_matmul_ref_matches_core():
    x = jax.random.normal(jax.random.PRNGKey(2), (32, 128))
    w = jax.random.normal(jax.random.PRNGKey(3), (128, 48))
    qt = quantize(x, 8, 4)
    y1 = aaq_matmul_ref(qt.inliers, qt.scales, qt.outlier_values,
                        qt.outlier_idx, w, bits=8)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(qmatmul(qt, w)),
                               rtol=1e-4, atol=1e-3)


FLASH_CASES = [
    dict(B=2, Sq=64, Skv=64, Hq=4, Hkv=4, D=32, causal=False, window=None,
         bias=False, kvlen=False),
    dict(B=2, Sq=64, Skv=64, Hq=4, Hkv=2, D=32, causal=True, window=None,
         bias=False, kvlen=False),
    dict(B=2, Sq=100, Skv=100, Hq=4, Hkv=1, D=32, causal=True, window=32,
         bias=False, kvlen=False),
    dict(B=4, Sq=48, Skv=48, Hq=2, Hkv=2, D=16, causal=False, window=None,
         bias=True, kvlen=False),
    dict(B=2, Sq=1, Skv=96, Hq=4, Hkv=2, D=32, causal=False, window=None,
         bias=False, kvlen=True),
    dict(B=2, Sq=33, Skv=70, Hq=2, Hkv=2, D=32, causal=False, window=None,
         bias=False, kvlen=True),
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kernel_vs_ref(case, dtype):
    c = case
    r = lambda s, k: jax.random.normal(jax.random.PRNGKey(k), s).astype(dtype)
    q = r((c["B"], c["Sq"], c["Hq"], c["D"]), 1)
    k = r((c["B"], c["Skv"], c["Hkv"], c["D"]), 2)
    v = r((c["B"], c["Skv"], c["Hkv"], c["D"]), 3)
    bias = r((1, c["Hq"], c["Sq"], c["Skv"]), 4) if c["bias"] else None
    kvlen = (jnp.array([c["Skv"] // 2, c["Skv"]] * (c["B"] // 2), jnp.int32)
             if c["kvlen"] else None)
    o_k = flash_mha_pallas(q, k, v, bias, kvlen, causal=c["causal"],
                           window=c["window"], block_q=32, block_k=32,
                           interpret=True)
    o_r = mha_ref(q, k, v, bias=bias, causal=c["causal"], window=c["window"],
                  kv_valid_len=kvlen)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32), rtol=tol, atol=tol)


def test_bias_block_broadcast_consistent_across_paths():
    """bias batch Bb < B broadcasts block-wise (entry t covers the B//Bb
    consecutive q rows starting at t*B//Bb) — the addressing triangular
    attention's protein-major row flattening requires.  All three
    implementations (ref, chunked, Pallas) must agree with an explicitly
    repeated bias."""
    bp, n, hq, d = 3, 16, 2, 8         # 3 proteins x 16 flattened rows
    b = bp * n
    r = lambda s, key: jax.random.normal(jax.random.PRNGKey(key), s)
    q, k, v = r((b, n, hq, d), 1), r((b, n, hq, d), 2), r((b, n, hq, d), 3)
    bias = r((bp, hq, n, n), 4)
    explicit = jnp.repeat(bias, n, axis=0)             # (b, hq, n, n)
    o_exp = mha_ref(q, k, v, bias=explicit)
    o_ref = mha_ref(q, k, v, bias=bias)
    np.testing.assert_array_equal(np.asarray(o_exp), np.asarray(o_ref))
    o_chk = mha_chunked(q, k, v, bias=bias, q_chunk=8)
    np.testing.assert_allclose(np.asarray(o_exp), np.asarray(o_chk),
                               rtol=2e-5, atol=2e-5)
    o_pal = flash_mha_pallas(q, k, v, bias, block_q=8, block_k=8,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(o_exp), np.asarray(o_pal),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal,window,bias", [(True, None, False),
                                                (True, 32, False),
                                                (False, None, True)])
def test_mha_chunked_vs_ref(causal, window, bias):
    B, S, Hq, Hkv, D = 2, 128, 4, 2, 16
    r = lambda s, k: jax.random.normal(jax.random.PRNGKey(k), s)
    q, k, v = r((B, S, Hq, D), 1), r((B, S, Hkv, D), 2), r((B, S, Hkv, D), 3)
    bb = r((1, Hq, S, S), 4) if bias else None
    o1 = mha_ref(q, k, v, bias=bb, causal=causal, window=window)
    o2 = mha_chunked(q, k, v, bias=bb, causal=causal, window=window,
                     q_chunk=32)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=2e-5,
                               atol=2e-5)
