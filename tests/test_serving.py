"""Serving-subsystem tests: bucket assignment, padding-mask correctness
(under the fold parity contract of ``_parity``), executable-cache hit
behavior (steady state = zero new compilations), token-budget batching,
and AAQ-aware admission control."""
import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _parity import assert_fold_parity

from repro.configs import reduce_ppm_config
from repro.core import make_scheme
from repro.models.ppm import init_ppm, ppm_forward
from repro.serving import (ADMIT, DEFER, REJECT, AdmissionController,
                           CompileWatcher, FoldEngine, FoldRequest,
                           TokenBudgetScheduler, pad_to_bucket, parse_buckets,
                           pow2_buckets)

CFG = reduce_ppm_config()
PARAMS = init_ppm(jax.random.PRNGKey(0), CFG)
SCHEME = make_scheme("lightnobel_aaq")
RNG = np.random.default_rng(7)


def _seq(length: int) -> np.ndarray:
    return RNG.integers(0, 20, length).astype(np.int32)


# --------------------------------------------------------------------------
# buckets
# --------------------------------------------------------------------------
def test_pow2_buckets_cover_range():
    assert pow2_buckets(24, 64) == (32, 64)
    assert pow2_buckets(16, 100) == (16, 32, 64, 128)
    assert parse_buckets("96,32,64", 0, 0) == (32, 64, 96)
    assert parse_buckets("pow2", 20, 40) == (32, 64)


def test_bucket_assignment_and_too_long():
    sched = TokenBudgetScheduler((32, 64))
    assert sched.bucket_for(1) == 32
    assert sched.bucket_for(32) == 32
    assert sched.bucket_for(33) == 64
    assert sched.bucket_for(65) is None
    rej = sched.submit(FoldRequest(0, _seq(80)), now=0.0)
    assert rej is not None and "exceeds max bucket" in rej.reason


# --------------------------------------------------------------------------
# padding-mask correctness
# --------------------------------------------------------------------------
@pytest.mark.parametrize("scheme", ["baseline_fp16", "lightnobel_aaq"])
def test_padded_forward_matches_padded_single(scheme):
    """Real-token coords from a mixed batch match the single-request (same
    bucket) forward under the parity contract — padding and batching never
    leak into real tokens.  Under FP the distogram is allclose too."""
    bucket, lens = 64, [48, 63, 40]
    seqs = [_seq(ln) for ln in lens]
    sch = make_scheme(scheme)
    fwd = jax.jit(lambda p, a, m: ppm_forward(p, a, CFG, sch, mask=m))
    aat, mask = pad_to_bucket(seqs, bucket)
    batched = fwd(PARAMS, jnp.asarray(aat), jnp.asarray(mask))
    for i, (s, ln) in enumerate(zip(seqs, lens)):
        a1, m1 = pad_to_bucket([s], bucket)
        single = fwd(PARAMS, jnp.asarray(a1), jnp.asarray(m1))
        assert_fold_parity(scheme, batched["coords"][i, :ln],
                           single["coords"][0, :ln])
        if scheme == "baseline_fp16":
            np.testing.assert_allclose(
                np.asarray(batched["distogram"][i, :ln, :ln]),
                np.asarray(single["distogram"][0, :ln, :ln]),
                rtol=1e-4, atol=1e-4)


def test_full_bucket_mask_is_noop():
    """mask of all-ones == the legacy unmasked path, bitwise."""
    s = _seq(32)
    aat = jnp.asarray(s)[None]
    ones = jnp.ones((1, 32), bool)
    out_mask = ppm_forward(PARAMS, aat, CFG, SCHEME, mask=ones)
    out_none = ppm_forward(PARAMS, aat, CFG, SCHEME)
    np.testing.assert_array_equal(np.asarray(out_mask["coords"]),
                                  np.asarray(out_none["coords"]))


@pytest.mark.parametrize("scheme", ["baseline_fp16", "lightnobel_aaq"])
def test_dummy_rows_do_not_change_real_rows(scheme):
    """Engine-style batch rounding: extra fully-masked rows are inert."""
    s = _seq(40)
    a1, m1 = pad_to_bucket([s], 64)
    a4, m4 = pad_to_bucket([s], 64, batch=4)
    sch = make_scheme(scheme)
    fwd = jax.jit(lambda p, a, m: ppm_forward(p, a, CFG, sch, mask=m))
    o1 = fwd(PARAMS, jnp.asarray(a1), jnp.asarray(m1))
    o4 = fwd(PARAMS, jnp.asarray(a4), jnp.asarray(m4))
    assert_fold_parity(scheme, o4["coords"][0, :40], o1["coords"][0, :40])
    assert np.isfinite(np.asarray(o4["coords"])).all()


def test_engine_matches_sequential():
    """The acceptance contract: engine-served coords match the bucketed
    sequential path's coords for real tokens, under the parity contract."""
    lens = [48, 63, 100, 41]
    seqs = [_seq(ln) for ln in lens]
    engine = FoldEngine(PARAMS, CFG, SCHEME, buckets=(64, 128),
                        max_tokens_per_batch=256, max_batch=4)
    results = engine.run(seqs)
    fwd = jax.jit(lambda p, a, m: ppm_forward(p, a, CFG, SCHEME, mask=m))
    for r, s in zip(results, seqs):
        assert r.ok
        bucket = engine.bucket_for(len(s))
        a1, m1 = pad_to_bucket([s], bucket)
        ref = fwd(PARAMS, jnp.asarray(a1), jnp.asarray(m1))
        assert_fold_parity(SCHEME, r.coords, ref["coords"][0, :len(s)])
        assert r.coords.shape == (len(s), 3)
        assert r.distogram.shape == (len(s), len(s), CFG.distogram_bins)


# --------------------------------------------------------------------------
# executable cache
# --------------------------------------------------------------------------
def test_cache_second_wave_zero_compilations():
    engine = FoldEngine(PARAMS, CFG, SCHEME, buckets=(32, 64),
                        max_tokens_per_batch=256, max_batch=4)
    wave = [_seq(ln) for ln in (20, 30, 40, 60)]
    engine.run(wave)
    n0 = engine.compile_count
    assert n0 == 2                         # one executable per (bucket, scheme)
    watcher = CompileWatcher()
    watcher.mark()
    engine.run([_seq(ln) for ln in (25, 33, 18, 50)])   # same bucket mix
    assert engine.compile_count == n0
    if watcher.available:                  # independent jax.monitoring check
        assert watcher.delta() == 0


def test_fidelity_adds_one_fp_executable_per_bucket():
    engine = FoldEngine(PARAMS, CFG, SCHEME, buckets=(32,), fidelity=True,
                        max_tokens_per_batch=64, max_batch=2)
    results = engine.run([_seq(20), _seq(28)])
    assert engine.compile_count == 2       # (32, aaq) + (32, fp16)
    for r in results:
        assert r.tm_vs_fp is not None and 0.9 < r.tm_vs_fp <= 1.0


# --------------------------------------------------------------------------
# scheduler: token-budget batching
# --------------------------------------------------------------------------
def test_token_budget_splits_batches():
    sched = TokenBudgetScheduler((32,), max_tokens_per_batch=64, max_batch=8)
    for i in range(5):
        assert sched.submit(FoldRequest(i, _seq(20)), now=float(i)) is None
    sizes = []
    while sched.pending:
        sizes.append(sched.next_batch().batch_size)
    assert sizes == [2, 2, 1]              # 2 * 32 tokens <= 64 per batch


def test_oversized_single_request_still_served_alone():
    # one request whose bucket alone exceeds the token budget: ESMFold rule
    sched = TokenBudgetScheduler((128,), max_tokens_per_batch=64)
    assert sched.submit(FoldRequest(0, _seq(100)), now=0.0) is None
    assert sched.next_batch().batch_size == 1


def test_scheduler_batches_chunked_buckets():
    """Buckets at/above the token-wise-MHA threshold batch like any other
    now that the chunked path's bias addressing is block-broadcast (the
    solo-bucket carve-out is gone)."""
    sched = TokenBudgetScheduler((256,), max_tokens_per_batch=1024,
                                 max_batch=4)
    for i in range(3):
        assert sched.submit(FoldRequest(i, _seq(200 + i)), now=float(i)) is None
    assert sched.next_batch().batch_size == 3


def test_chunked_bucket_batch_matches_batch1():
    """The acceptance contract for the chunked-bias fix under the engine:
    a multi-protein N>=256 bucket (token-wise MHA path, batch 2) yields
    the coords of serving each protein alone in the same bucket, under
    the parity contract."""
    seqs = [_seq(200), _seq(230)]
    engine = FoldEngine(PARAMS, CFG, SCHEME, buckets=(256,),
                        max_tokens_per_batch=512, max_batch=2)
    assert engine.batch_for_bucket(256) == 2
    results = engine.run(seqs)
    assert all(r.ok and r.bucket == 256 and r.batch_size == 2
               for r in results)
    solo = FoldEngine(PARAMS, CFG, SCHEME, buckets=(256,),
                      max_tokens_per_batch=256, max_batch=1)
    assert solo.batch_for_bucket(256) == 1
    for r, s in zip(results, seqs):
        [r1] = solo.run([s])
        assert_fold_parity(SCHEME, r.coords, r1.coords)


def test_scheduler_cancel_is_indexed_not_scanned():
    """Cancellation pops the O(1) id index; the deque tombstone is
    compacted lazily and never reaches a batch, pending, or expiry."""
    sched = TokenBudgetScheduler((32, 64), max_tokens_per_batch=1024,
                                 max_batch=8)
    for i in range(20):
        assert sched.submit(FoldRequest(i, _seq(20 + (i % 2) * 20)),
                            now=float(i)) is None
    assert sched.pending == 20
    assert sched.cancel(3) and sched.cancel(4) and sched.cancel(19)
    assert not sched.cancel(3)            # already cancelled
    assert not sched.cancel(999)          # never queued
    assert sched.pending == 17            # index, not deque length
    served = []
    while sched.pending:
        served += [r.request_id for r in sched.next_batch().requests]
    assert len(served) == 17
    assert not {3, 4, 19} & set(served)
    assert not sched.cancel(served[0])    # left the queue: cancel is False


def test_cancelled_request_never_resurrects_as_expired():
    sched = TokenBudgetScheduler((32,))
    req = FoldRequest(0, _seq(20), deadline_s=1.0)
    sched.submit(req, now=0.0)
    assert sched.cancel(0)
    assert sched.purge_expired(now=100.0) == []   # tombstone, not expiry
    assert sched.pending == 0 and sched.next_batch() is None


def test_fcfs_across_buckets():
    sched = TokenBudgetScheduler((32, 64), max_tokens_per_batch=512)
    sched.submit(FoldRequest(0, _seq(50)), now=1.0)    # bucket 64, oldest
    sched.submit(FoldRequest(1, _seq(20)), now=2.0)    # bucket 32
    assert sched.next_batch().bucket == 64
    assert sched.next_batch().bucket == 32


# --------------------------------------------------------------------------
# admission control
# --------------------------------------------------------------------------
def test_admission_pricing_monotone_and_scheme_aware():
    aaq = AdmissionController(CFG, SCHEME)
    fp = AdmissionController(CFG, make_scheme("baseline_fp16"))
    assert aaq.estimate_bytes(64, 1) > aaq.estimate_bytes(32, 1)
    assert aaq.estimate_bytes(32, 4) > aaq.estimate_bytes(32, 1)
    # AAQ packs the pair inventory far below fp16
    assert aaq.estimate_bytes(64, 1) < fp.estimate_bytes(64, 1)
    bd = aaq.explain(64, 2)
    assert bd["total_mb"] == pytest.approx(
        bd["pair_mb"] + bd["score_mb"] + bd["residual_mb"])


def test_admission_verdicts_deterministic():
    one = AdmissionController(CFG, SCHEME).estimate_bytes(64, 1)
    ctl = AdmissionController(CFG, SCHEME, mem_budget_bytes=one)
    assert ctl.admit(64, 1).verdict == ADMIT
    assert ctl.admit(64, 2).verdict == DEFER
    small = AdmissionController(CFG, SCHEME, mem_budget_bytes=one // 2)
    assert small.admit(64, 1).verdict == REJECT
    assert small.max_batch_for(64, 4) == 0


def test_engine_rejects_over_budget_and_bounds_peak():
    # budget sized to admit bucket 32 alone but never bucket 64
    ctl = AdmissionController(CFG, SCHEME)
    budget_mb = (ctl.estimate_bytes(64, 1) - 1) / 1e6
    assert ctl.estimate_bytes(32, 1) < budget_mb * 1e6
    engine = FoldEngine(PARAMS, CFG, SCHEME, buckets=(32, 64),
                        max_tokens_per_batch=256, max_batch=4,
                        mem_budget_mb=budget_mb)
    results = engine.run([_seq(20), _seq(50), _seq(28)])
    by_id = {r.request_id: r for r in results}
    assert by_id[1].status == "rejected" and "budget" in by_id[1].reason
    served = [r for r in results if r.ok]
    assert {r.request_id for r in served} == {0, 2}
    assert all(r.est_activation_bytes <= budget_mb * 1e6 for r in served)


def test_admission_budget_shrinks_static_batch():
    ctl = AdmissionController(CFG, SCHEME)
    two = ctl.estimate_bytes(32, 2)
    engine = FoldEngine(PARAMS, CFG, SCHEME, buckets=(32,),
                        max_tokens_per_batch=1024, max_batch=8,
                        mem_budget_mb=two / 1e6)
    assert engine.batch_for_bucket(32) == 2
    results = engine.run([_seq(20)] * 5)
    assert all(r.ok for r in results)
    assert all(r.est_activation_bytes <= two for r in results)
    assert max(r.batch_size for r in results) <= 2


# --------------------------------------------------------------------------
# kernel-backend recording
# --------------------------------------------------------------------------
def test_results_record_kernel_backend():
    """Every served batch records the dispatch backend it was lowered
    under — the --report column the --kernels flag is audited by."""
    import io as _io

    from repro.serving.metrics import csv_row

    engine = FoldEngine(PARAMS, CFG, SCHEME, buckets=(32,), kernels="ref",
                        max_tokens_per_batch=64, max_batch=2)
    [r] = engine.run([_seq(20)])
    assert r.kernel_backend == "ref"
    # backend + placement + chunk_size columns
    assert csv_row(r).endswith(",ref,single,0")
    buf = _io.StringIO()
    engine.metrics.write_json(buf)
    assert '"kernel_backend": "ref"' in buf.getvalue()
    with pytest.raises(ValueError):
        FoldEngine(PARAMS, CFG, SCHEME, kernels="cuda")


# --------------------------------------------------------------------------
# metrics / reports
# --------------------------------------------------------------------------
def test_metrics_report_shapes():
    engine = FoldEngine(PARAMS, CFG, SCHEME, buckets=(32,), fidelity=True,
                        max_tokens_per_batch=64, max_batch=2)
    engine.run([_seq(20), _seq(30), _seq(25)])
    s = engine.metrics.summary()
    assert s["served"] == 3 and s["rejected"] == 0
    assert s["tokens"] == 75 and s["tokens_per_s"] > 0
    assert s["compiles"] == 2
    [b] = s["buckets"]
    assert b["bucket"] == 32 and 0.0 < b["padding_waste"] < 1.0
    csv = io.StringIO()
    engine.metrics.write_csv(csv)
    lines = csv.getvalue().strip().splitlines()
    assert len(lines) == 4 and lines[0].startswith("request,")
    js = io.StringIO()
    engine.metrics.write_json(js)
    assert '"summary"' in js.getvalue()
