"""Kernel-dispatch tests: backend selection + trace counters, the direct
chunked-B>1 triangular-attention parity (the fixed flattened-row bias
addressing), and the Pallas<->ref parity suite on the full PPM forward —
{pallas-interpret, ref} x {fp32, AAQ} x B in {1,2} x N in {64, 300}.
N=300 exercises the chunked token-wise path (>= CHUNKED_ATTN_LEN), N=64
the explicit-pallas routing below the chunk threshold."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import esmfold_ppm
from repro.core import make_scheme, quantize
from repro.core.schemes import FP16Baseline
from repro.kernels import dispatch
from repro.models.ppm import init_ppm, ppm_forward, tm_score
from repro.models.ppm import trunk as tk
from repro.models.ppm.trunk import PPMConfig

# Deliberately tiny: the N=300 pallas-interpret runs execute the real
# kernel grids; model width only scales the constant factor.
CFG = PPMConfig(blocks=1, hm=32, hz=16, seq_heads=2, pair_heads=2,
                tri_hidden=16, recycles=1, ipa_iters=1)
PARAMS = init_ppm(jax.random.PRNGKey(0), CFG)


# --------------------------------------------------------------------------
# backend selection / counters
# --------------------------------------------------------------------------
def test_backend_mode_roundtrip_and_validation():
    assert dispatch.get_backend() == dispatch.AUTO
    with pytest.raises(ValueError):
        dispatch.set_backend("cuda")
    with dispatch.use_backend(dispatch.REF):
        assert dispatch.get_backend() == dispatch.REF
        with dispatch.use_backend(dispatch.PALLAS):
            assert dispatch.get_backend() == dispatch.PALLAS
        assert dispatch.get_backend() == dispatch.REF
    assert dispatch.get_backend() == dispatch.AUTO


def test_auto_resolution_and_describe_off_tpu():
    if jax.default_backend() == "tpu":
        pytest.skip("auto resolves to pallas on TPU")
    assert dispatch.resolve_attention(512, 512) == dispatch.REF
    assert dispatch.resolve_matmul(4096) == dispatch.REF
    assert dispatch.describe() == "auto:ref"
    assert dispatch.describe(dispatch.REF) == "ref"
    assert dispatch.describe(dispatch.PALLAS) == "pallas-interpret"
    # off-TPU both per-op resolutions are ref, so shape hints never split
    assert dispatch.describe(dispatch.AUTO, seq=512) == "auto:ref"
    assert dispatch.describe(dispatch.AUTO, seq=512,
                             qmm_tokens=4) == "auto:ref"
    assert dispatch.interpret_mode()


def test_describe_reports_split_auto_resolutions(monkeypatch):
    """Auto-mode labels must fold in BOTH dispatch floors: a bucket whose
    attention clears MIN_FLASH_SEQ but whose matmuls fall below
    MIN_QMM_TOKENS (and vice versa) is reported as the split it actually
    runs, not whichever the attention floor alone says."""
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    assert not dispatch.interpret_mode()
    # agree high / agree low: one label
    assert dispatch.describe(dispatch.AUTO, seq=256) == "auto:pallas"
    assert dispatch.describe(dispatch.AUTO, seq=32,
                             qmm_tokens=8) == "auto:ref"
    # attention pallas, matmul ref (tiny token count)
    assert dispatch.describe(dispatch.AUTO, seq=256,
                             qmm_tokens=8) == "auto:attn=pallas;qmm=ref"
    # the reported bug's converse: bucket below MIN_FLASH_SEQ whose
    # pair-dataflow token count (seq**2 default) clears MIN_QMM_TOKENS
    assert dispatch.describe(dispatch.AUTO,
                             seq=64) == "auto:attn=ref;qmm=pallas"
    # qmm_tokens alone gives no attention shape to resolve: the attention
    # half must be reported unknown, not guessed capability-only
    assert dispatch.describe(dispatch.AUTO,
                             qmm_tokens=4096) == "auto:attn=?;qmm=pallas"
    assert dispatch.describe(dispatch.AUTO,
                             qmm_tokens=8) == "auto:attn=?;qmm=ref"
    # explicit modes are unaffected by the hints
    assert dispatch.describe(dispatch.REF, seq=256, qmm_tokens=8) == "ref"
    # the split label must survive a CSV row (no commas)
    assert "," not in dispatch.describe(dispatch.AUTO, seq=256, qmm_tokens=8)


def test_explicit_backend_arg_overrides_mode():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 2, 8))
    with dispatch.use_backend(dispatch.REF):
        dispatch.reset_counters()
        dispatch.attention(q, q, q, backend=dispatch.PALLAS)
        assert dispatch.counters["attention.pallas"] == 1
        assert dispatch.counters["attention.ref"] == 0


def test_counters_count_traces_not_executions():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 2, 8))
    with dispatch.use_backend(dispatch.REF):
        dispatch.reset_counters()
        f = jax.jit(lambda a: dispatch.attention(a, a, a))
        f(q)
        assert dispatch.counters["attention.ref"] == 1
        f(q)   # executable-cache hit: no new trace, no new count
        assert dispatch.counters["attention.ref"] == 1


@pytest.mark.parametrize("n,chunk", [(256, None), (1024, 256)])
def test_served_folds_hold_the_key_range_resident(n, chunk):
    """At esmfold_ppm widths a bucketed fold (N=256) and a chunked long
    fold (N=1,024, 256-row chunks) trace every Pallas attention call (the
    triangular, sequence and structure-module ones) in the form with the
    whole key range in one block; a causal LM call streams its keys."""
    cfg = dataclasses.replace(esmfold_ppm.CONFIG, blocks=1)
    params = jax.eval_shape(lambda: init_ppm(jax.random.PRNGKey(0), cfg))
    aat = jax.ShapeDtypeStruct((2, n), jnp.int32)
    mask = jax.ShapeDtypeStruct((2, n), jnp.bool_)
    with dispatch.use_backend(dispatch.PALLAS):
        dispatch.reset_counters()
        jax.eval_shape(lambda p, a, m: ppm_forward(
            p, a, cfg, FP16Baseline(), mask=m, chunk_size=chunk),
            params, aat, mask)
        c = dict(dispatch.counters)
        assert c["attention.pallas"] >= 3
        assert c["attention.pallas.kv_resident"] == c["attention.pallas"]
        assert c["attention.pallas.kv_streamed"] == 0

        dispatch.reset_counters()
        q = jax.ShapeDtypeStruct((1, n, 4, 32), jnp.bfloat16)
        jax.eval_shape(lambda a: dispatch.attention(a, a, a, causal=True), q)
        assert dispatch.counters["attention.pallas.kv_streamed"] == 1
        assert dispatch.counters["attention.pallas.kv_resident"] == 0


@pytest.mark.parametrize("bits,k", [(8, 4), (4, 4), (4, 0)])
def test_quantized_linear_pallas_matches_ref(bits, k):
    x = jax.random.normal(jax.random.PRNGKey(0), (96, 128)) * 2
    x = x.at[3, 7].set(-60.0)
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 48))
    yr = dispatch.quantized_linear(x, w, bits=bits, k_outliers=k,
                                   backend=dispatch.REF)
    yp = dispatch.quantized_linear(x, w, bits=bits, k_outliers=k,
                                   backend=dispatch.PALLAS)
    sc = float(jnp.max(quantize(x, bits, k).scales))
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yr), rtol=2e-2,
                               atol=2 * sc * np.sqrt(128))


# --------------------------------------------------------------------------
# chunked triangular attention, batch > 1 (the fixed bias addressing)
# --------------------------------------------------------------------------
def _tri_attn_params():
    p = init_ppm(jax.random.PRNGKey(3), CFG)["trunk"]
    # stacked (blocks=1) -> single block; randomize the zero-init output
    # projections so the parity is non-trivial
    p = jax.tree.map(lambda a: a[0], p)["tri_attn_start"]
    return jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(4), a.shape) * 0.1, p)


@pytest.mark.parametrize("backend", [dispatch.REF, dispatch.PALLAS])
@pytest.mark.parametrize("masked", [False, True])
def test_tri_attn_chunked_b3_matches_unchunked(monkeypatch, backend, masked):
    """Direct parity: the chunked (token-wise, flattened-row) path at B=3
    against the unchunked cubic reference.  Before the block-broadcast bias
    fix the chunked path addressed bias rows modulo the protein batch and
    this failed for every row past the first protein."""
    heads, n, b = CFG.pair_heads, 48, 3
    p = _tri_attn_params()
    z = jax.random.normal(jax.random.PRNGKey(5), (b, n, n, CFG.hz))
    mask = None
    if masked:
        mask = jnp.arange(n)[None, :] < jnp.array([n, 37, 20])[:, None]
    scheme = FP16Baseline()

    monkeypatch.setattr(tk, "CHUNKED_ATTN_LEN", 1 << 30)
    with dispatch.use_backend(dispatch.REF):
        o_ref = tk.tri_attn_apply(p, z, scheme, True, "t", heads, mask=mask)

    monkeypatch.setattr(tk, "CHUNKED_ATTN_LEN", 16)
    with dispatch.use_backend(backend):
        dispatch.reset_counters()
        o_chk = tk.tri_attn_apply(p, z, scheme, True, "t", heads, mask=mask)
        assert dispatch.counters[f"attention.{backend}"] == 1

    d = jnp.abs(o_ref - o_chk)
    if mask is not None:   # padded positions never reach a consumer
        d = d * (mask[:, :, None] & mask[:, None, :])[..., None]
    assert float(jnp.max(d)) < 2e-5


# --------------------------------------------------------------------------
# full-forward parity suite
# --------------------------------------------------------------------------
def _forward(scheme, aat):
    out = jax.jit(lambda p, a: ppm_forward(p, a, CFG, scheme))(PARAMS, aat)
    return {"coords": np.asarray(out["coords"]), "z": np.asarray(out["z"])}


@pytest.mark.parametrize("scheme_name", ["baseline_fp16", "lightnobel_aaq"])
@pytest.mark.parametrize("batch,n", [(1, 64), (2, 64), (1, 300), (2, 300)])
def test_ppm_forward_pallas_matches_ref(scheme_name, batch, n):
    """The acceptance contract: with the pallas backend the compiled PPM
    forward contains ONLY Pallas attention (and, for AAQ, Pallas quantized
    matmuls) — proven by the trace counters — and its outputs match the
    ref backend.  fp32 parity is numeric (the flash kernel reorders the
    softmax, so bitwise is not expected); AAQ parity is structural
    (TM-score), since the ref's unchunked path additionally fake-quants
    attention probabilities (a site the fused kernel never materializes)
    and quantization rounding ties may fall differently per kernel."""
    aat = jax.random.randint(jax.random.PRNGKey(7), (batch, n), 0, 20)
    scheme = make_scheme(scheme_name)

    with dispatch.use_backend(dispatch.REF):
        dispatch.reset_counters()
        ref = _forward(scheme, aat)
        assert dispatch.counters["attention.ref"] > 0
        assert dispatch.counters["attention.pallas"] == 0
        assert dispatch.counters["qmatmul.pallas"] == 0

    with dispatch.use_backend(dispatch.PALLAS):
        dispatch.reset_counters()
        pal = _forward(scheme, aat)
        assert dispatch.counters["attention.pallas"] > 0
        assert dispatch.counters["attention.ref"] == 0
        if scheme_name == "lightnobel_aaq":
            assert dispatch.counters["qmatmul.pallas"] > 0
            assert dispatch.counters["qmatmul.ref"] == 0

    for out in (ref, pal):
        assert np.isfinite(out["coords"]).all()
        assert np.isfinite(out["z"]).all()
    if scheme_name == "baseline_fp16":
        np.testing.assert_allclose(pal["coords"], ref["coords"],
                                   rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(pal["z"], ref["z"], rtol=1e-3, atol=2e-4)
    else:
        for i in range(batch):
            tm = float(tm_score(jnp.asarray(pal["coords"][i]),
                                jnp.asarray(ref["coords"][i])))
            assert tm > 0.95, (i, tm)
