"""Pipelined-execution tests: dispatch/retire ring semantics, bitwise
parity across in-flight depths and schemes, occupancy-fitted launch sizing,
the fill-or-timeout linger policy, deadline expiry while a batch is in
flight, and lazy distogram fetching after the engine has moved on.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _parity import assert_fold_parity

from repro.configs import reduce_ppm_config
from repro.core import make_scheme
from repro.models.ppm import init_ppm, ppm_forward
from repro.serving import (FoldClient, FoldEngine, FoldRequest,
                           LazyDistogram, TokenBudgetScheduler,
                           pad_to_bucket)
from repro.serving import events as ev

CFG = reduce_ppm_config()
PARAMS = init_ppm(jax.random.PRNGKey(0), CFG)
RNG = np.random.default_rng(29)


def _seq(length: int) -> np.ndarray:
    return RNG.integers(0, 20, length).astype(np.int32)


class ManualClock:
    def __init__(self, t: float = 500.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# --------------------------------------------------------------------------
# bitwise parity across depths and schemes
# --------------------------------------------------------------------------
LENS = (10, 20, 30, 12, 28, 9)          # mixed buckets: 16 and 32


@pytest.mark.parametrize("scheme", ["baseline_fp16", "lightnobel_aaq"])
def test_pipeline_bitwise_parity_across_depths(scheme):
    """The hard numerics contract: a pipelined run (depth 2, 3) must be
    bitwise identical to the depth-1 synchronous path — same coords, same
    distograms — with compile_count unchanged across depths (launch shapes
    must not depend on overlap)."""
    seqs = [_seq(ln) for ln in LENS]

    def run(client):
        handles = [client.submit(s) for s in seqs]
        client.drive()
        return [h.result() for h in handles]

    ref_client = FoldClient(PARAMS, CFG, scheme, buckets=(16, 32),
                            max_tokens_per_batch=64, max_batch=4,
                            inflight_depth=1)
    ref = run(ref_client)
    core = ref_client.core
    compiles = core.compile_count
    assert all(r.ok for r in ref)

    for depth in (2, 3):
        core.inflight_depth = depth       # same core: warm executables
        piped = run(FoldClient(PARAMS, CFG, scheme, core=core))
        assert core.compile_count == compiles, \
            f"depth {depth} changed launch shapes"
        assert core.metrics.max_inflight >= 2
        for a, b in zip(ref, piped):
            assert b.ok and a.bucket == b.bucket
            assert a.launched_batch == b.launched_batch
            np.testing.assert_array_equal(a.coords, b.coords)
            np.testing.assert_array_equal(np.asarray(a.distogram),
                                          np.asarray(b.distogram))


# --------------------------------------------------------------------------
# ring mechanics
# --------------------------------------------------------------------------
def test_dispatch_ring_bounded_and_execute_needs_empty_ring():
    client = FoldClient(PARAMS, CFG, "lightnobel_aaq", buckets=(16,),
                        max_tokens_per_batch=16, max_batch=1,
                        inflight_depth=2)
    core, sched = client.core, client.scheduler
    now = 0.0
    for i in range(3):
        assert sched.submit(FoldRequest(i, _seq(10)), now) is None
    b1, b2, b3 = sched.next_batch(), sched.next_batch(), sched.next_batch()
    core.dispatch(b1)
    core.dispatch(b2)
    assert core.inflight_count == 2 and core.inflight_full
    with pytest.raises(RuntimeError, match="ring full"):
        core.dispatch(b3)
    with pytest.raises(RuntimeError, match="empty in-flight ring"):
        core.execute(b3)
    first = core.retire()
    assert [r.request_id for r in first] == [0]      # FIFO: oldest first
    assert core.inflight_count == 1
    second = core.retire()
    assert [r.request_id for r in second] == [1]
    assert core.retire() == []                       # empty ring: no-op
    # the ring drained, execute works again (dispatch + immediate retire)
    [r3] = core.execute(b3)
    assert r3.ok and r3.request_id == 2
    assert r3.coords.shape == (10, 3) and core.inflight_count == 0


def test_inflight_cap_respected_under_thread_driver():
    client = FoldClient(PARAMS, CFG, "lightnobel_aaq", buckets=(16,),
                        max_tokens_per_batch=16, max_batch=1,
                        inflight_depth=2)
    handles = [client.submit(_seq(10 + i % 3)) for i in range(5)]
    client.start()                       # 5 one-request batches queued
    for h in handles:
        assert h.result(timeout=600.0).ok
    client.stop()
    s = client.metrics.summary()
    assert s["pipeline"]["inflight_depth"] == 2
    assert s["pipeline"]["max_inflight"] == 2        # pipelined, capped
    assert s["pipeline"]["batches"] == 5


# --------------------------------------------------------------------------
# occupancy-fitted launch sizing
# --------------------------------------------------------------------------
def test_launch_size_fits_occupancy_and_reuses_cached_sizes():
    engine = FoldEngine(PARAMS, CFG, "lightnobel_aaq", buckets=(16,),
                        max_tokens_per_batch=64, max_batch=4)
    assert engine.batch_for_bucket(16) == 4          # the cap, not the size

    full = engine.run([_seq(12) for _ in range(4)])
    assert engine.compile_count == 1                 # (16, b=4)
    assert all(r.launched_batch == 4 and r.batch_size == 4 for r in full)

    three = engine.run([_seq(12) for _ in range(3)])
    # one dummy row is cheaper than a fresh compile: reuse the cached 4
    assert engine.compile_count == 1
    assert all(r.launched_batch == 4 and r.batch_size == 3 for r in three)
    assert all(0.0 < r.occupancy < 1.0 for r in three)

    two = engine.run([_seq(12), _seq(12)])
    # two dummy rows bust the waste guard (max(1, n//2) = 1): exact fit
    assert engine.compile_count == 2                 # + (16, b=2)
    assert all(r.launched_batch == 2 for r in two)

    one = engine.run([_seq(12)])
    assert engine.compile_count == 2                 # reuses (16, b=2)
    assert all(r.launched_batch == 2 for r in one)

    # occupancy = real tokens / (launched rows * bucket), and it rides the
    # CSV report
    r = three[0]
    assert r.occupancy == pytest.approx(3 * 12 / (4 * 16))
    from repro.serving import CSV_HEADER, csv_row
    assert ",occupancy," in CSV_HEADER
    occ_col = CSV_HEADER.split(",").index("occupancy")
    assert float(csv_row(r).split(",")[occ_col]) == pytest.approx(
        r.occupancy, abs=1e-3)


def test_exact_fit_batches_match_static_padding():
    """An occupancy-fitted launch (2 real rows at size 2) matches the same
    requests padded into a max-size batch under the parity contract — the
    FLOP savings cost no fidelity."""
    seqs = [_seq(48), _seq(56)]
    small = FoldEngine(PARAMS, CFG, "lightnobel_aaq", buckets=(64,),
                       max_tokens_per_batch=128, max_batch=2)  # cap 2
    big = FoldEngine(PARAMS, CFG, "lightnobel_aaq", buckets=(64,),
                     max_tokens_per_batch=256, max_batch=4)    # cap 4
    big.core._executable(64, 4, big.core.scheme)   # force the padded shape
    big_res = big.run(seqs + [_seq(52), _seq(44)])
    small_res = small.run(seqs)
    assert all(r.launched_batch == 2 for r in small_res)
    assert all(r.launched_batch == 4 for r in big_res)
    for a, b in zip(small_res, big_res[:2]):
        assert_fold_parity("lightnobel_aaq", a.coords, b.coords)


# --------------------------------------------------------------------------
# fill-or-timeout linger (scheduler-level: deterministic, no forwards)
# --------------------------------------------------------------------------
def test_linger_holds_underfull_batch_until_fill_or_timeout():
    sched = TokenBudgetScheduler((16,), max_tokens_per_batch=64,
                                 max_batch=4, linger_ms=100.0)
    assert sched.submit(FoldRequest(0, _seq(10)), now=0.0) is None
    # inside the linger window and fillable: held
    assert sched.next_batch(now=0.05) is None
    assert sched.linger_holds == 1
    assert sched.hold_until == pytest.approx(0.1)
    assert sched.pending == 1                        # still queued
    # arrivals fill the batch: launches immediately, full
    for i in range(1, 4):
        sched.submit(FoldRequest(i, _seq(10)), now=0.06)
    batch = sched.next_batch(now=0.07)
    assert batch is not None and batch.batch_size == 4
    # timeout path: a lone request launches once the window passes
    sched.submit(FoldRequest(9, _seq(10)), now=1.0)
    assert sched.next_batch(now=1.05) is None        # held again
    batch = sched.next_batch(now=1.2)                # past arrival+100ms
    assert batch is not None and batch.batch_size == 1


def test_linger_window_anchored_to_earliest_arrival_not_priority():
    """A late high-priority arrival re-sorts the batch head but must not
    extend the hold past the OLDEST request's linger budget."""
    sched = TokenBudgetScheduler((16,), max_tokens_per_batch=64,
                                 max_batch=4, linger_ms=100.0)
    sched.submit(FoldRequest(0, _seq(10), priority=0), now=0.0)
    sched.submit(FoldRequest(1, _seq(10), priority=5), now=0.09)
    # 0.12 is inside the high-priority request's own window (0.09 + 0.1)
    # but past the oldest arrival's budget (0.0 + 0.1): launch now
    batch = sched.next_batch(now=0.12)
    assert batch is not None and batch.batch_size == 2
    assert batch.requests[0].request_id == 1      # priority still leads


def test_linger_bypassed_when_draining_and_for_stopped_growth():
    sched = TokenBudgetScheduler((16,), max_tokens_per_batch=64,
                                 max_batch=4, linger_ms=100.0)
    sched.submit(FoldRequest(0, _seq(10)), now=0.0)
    # a draining pump forces the launch (no future arrivals can fill it)
    assert sched.next_batch(now=0.01, allow_linger=False) is not None
    # growth stopped by max_batch is NOT underfull-because-empty: launches
    for i in range(1, 6):
        sched.submit(FoldRequest(i, _seq(10)), now=0.0)
    batch = sched.next_batch(now=0.01)
    assert batch is not None and batch.batch_size == 4   # full batch
    # ...and the 1-request remainder is held again
    assert sched.next_batch(now=0.01) is None
    assert sched.next_batch(now=0.2) is not None


def test_held_bucket_yields_to_launchable_bucket():
    sched = TokenBudgetScheduler((16, 32), max_tokens_per_batch=64,
                                 max_batch=2, linger_ms=100.0)
    sched.submit(FoldRequest(0, _seq(10)), now=0.95)     # bucket 16, urgent
    sched.submit(FoldRequest(1, _seq(30)), now=1.0)      # bucket 32
    sched.submit(FoldRequest(2, _seq(30)), now=1.0)      # fills bucket 32
    batch = sched.next_batch(now=1.01)   # inside bucket 16's linger window
    # bucket 16 is most urgent but lingering; the full bucket-32 batch
    # runs during the hold instead of idling
    assert batch is not None and batch.bucket == 32 and batch.batch_size == 2
    assert sched.linger_holds == 1


def test_linger_fills_batch_under_thread_driver():
    """End to end: with linger on, a second same-bucket submit inside the
    window rides the first request's batch instead of a second launch."""
    client = FoldClient(PARAMS, CFG, "lightnobel_aaq", buckets=(16,),
                        max_tokens_per_batch=32, max_batch=2,
                        inflight_depth=2, linger_ms=2000.0)
    client.warmup()                     # compile before the timing window
    client.start()
    h1 = client.submit(_seq(10))
    time.sleep(0.1)                     # well inside the linger window
    h2 = client.submit(_seq(12))
    r1, r2 = h1.result(timeout=600.0), h2.result(timeout=600.0)
    client.stop()
    assert r1.ok and r2.ok
    assert r1.batch_size == 2 and r2.batch_size == 2     # one shared batch
    assert client.metrics.summary()["pipeline"]["linger_holds"] >= 1


# --------------------------------------------------------------------------
# deadline expiry while a batch is in flight
# --------------------------------------------------------------------------
def test_deadline_expiry_while_batch_in_flight():
    clock = ManualClock()
    client = FoldClient(PARAMS, CFG, "lightnobel_aaq", buckets=(16,),
                        max_tokens_per_batch=16, max_batch=1,
                        inflight_depth=2, clock=clock)
    stream = client.stream()
    a = client.submit(_seq(10))
    b = client.submit(_seq(11))
    doomed = client.submit(_seq(12), deadline_s=5.0)
    served_first = client.drive(max_batches=1)    # dispatch a+b, retire a
    assert [r.request_id for r in served_first] == [a.request_id]
    assert client.core.inflight_count == 1        # b still in flight
    clock.advance(10.0)                           # doomed expires queued
    rest = client.drive()
    assert doomed.status == "EXPIRED"
    assert b.status == "DONE"
    statuses = {r.request_id: r.status for r in rest}
    assert statuses[doomed.request_id] == "expired"
    assert statuses[b.request_id] == "ok"
    # the expiry was processed BEFORE b's batch completed: its EXPIRED
    # event sequences ahead of b's BATCH_DONE
    evs = stream.events()
    expired_seq = next(e.seq for e in evs if e.kind == ev.EXPIRED)
    b_done_seq = next(e.seq for e in evs if e.kind == ev.BATCH_DONE
                      and e.request_id == b.request_id)
    assert expired_seq < b_done_seq


# --------------------------------------------------------------------------
# lazy distogram
# --------------------------------------------------------------------------
def test_lazy_distogram_fetch_after_engine_moved_on():
    client = FoldClient(PARAMS, CFG, "lightnobel_aaq", buckets=(16,),
                        max_tokens_per_batch=32, max_batch=2,
                        inflight_depth=2)
    s0 = _seq(12)
    h0 = client.submit(s0)
    client.drive()
    r0 = h0.result()
    assert isinstance(r0.distogram, LazyDistogram)
    assert not r0.distogram.materialized
    assert r0.distogram.shape == (12, 12, CFG.distogram_bins)  # no fetch
    assert not r0.distogram.materialized

    # the engine moves on: more batches dispatched, retired, delivered
    later = [client.submit(_seq(ln)) for ln in (10, 14, 11)]
    client.drive()
    assert all(h.result().ok for h in later)

    # first fetch materializes exactly this request's stripped rows,
    # bitwise-equal to the padded batch-1 reference forward
    got = np.asarray(r0.distogram)
    assert r0.distogram.materialized
    aat, mask = pad_to_bucket([s0], 16, 2)
    scheme = make_scheme("lightnobel_aaq")
    ref = jax.jit(lambda p, a, m: ppm_forward(p, a, CFG, scheme, mask=m))(
        PARAMS, jnp.asarray(aat), jnp.asarray(mask))
    np.testing.assert_array_equal(
        got, np.asarray(ref["distogram"][0, :12, :12]))
    # repeated access returns the cached slice, and indexing works
    assert r0.distogram.fetch() is r0.distogram.fetch()
    np.testing.assert_array_equal(r0.distogram[0, 0], got[0, 0])
