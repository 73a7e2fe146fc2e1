"""EngineCore: pipelined bucketed-compilation batch executor for PPM
serving.

The core owns (params, config, scheme) plus the compiled-executable cache
and executes ``ScheduledBatch``es; it has no queue and no policy.  Request
intake, ordering, priorities, deadlines, and cancellation live one layer up
in ``repro.serving.client.FoldClient``, whose pump loop drives this core.
``FoldEngine`` (bottom of this module) is the legacy ``submit/step/run``
surface, kept as a thin compatibility wrapper over a client.

Everything *model-specific* — the traced forward and its input specs, host
padding, the admission cost model, and the retire-side transfer/result
construction — lives in a ``repro.serving.workload.Workload`` plugin
(default: ``FoldWorkload``, the fold path this engine used to inline).
The core keeps everything substrate: the executable cache and its
(bucket, launch_batch, scheme, placement, chunk) key, launch-size fitting,
the in-flight ring, span tracing, and metrics plumbing.

Execution is a two-stage ``dispatch()``/``retire()`` pipeline over a
bounded in-flight ring (``inflight_depth``, default 2):

  * ``dispatch(batch)`` resolves the executable (compiling on a cold
    bucket), pads on the host, puts inputs on device, and *launches*
    without blocking — JAX dispatch is async, so the call returns while
    the device computes.  The fidelity FP re-run is launched async here
    too, instead of serializing after the main forward.
  * ``retire()`` blocks on the OLDEST in-flight batch, performs one host
    transfer of its coords, and hands each request a *lazy* distogram
    handle (``LazyDistogram``) — for long sequences the B x N x N x bins
    distogram is the peak host-memory term, so it is fetched only when a
    consumer asks.

While batch *k* computes on device, batch *k+1*'s padding/device-put and
batch *k-1*'s stripping run on the host.  ``execute()`` remains as the
synchronous composition (dispatch + immediate retire; requires an empty
ring) and is bitwise-identical to the pipelined path — same executables,
same padded inputs, in the same order.

Core responsibilities:

  * length buckets — every request is right-padded to its bucket edge, so
    the XLA shape space is the bucket set, not the set of observed lengths;
  * a compiled-executable cache keyed by ``(bucket, launch_batch, scheme,
    placement, chunk)``.  ``batch_for_bucket`` (token budget, max-batch cap, and
    the admission controller's memory cap) is the launch-size CAP; each
    batch launches at its occupancy fit — the real request count, or a
    slightly larger already-compiled size when the extra dummy rows are
    cheaper than a fresh multi-second compile (waste guard: at most
    ``max(1, n // 2)`` dummy rows).  The size space is finite and
    trace-determined, so steady-state serving still performs zero
    recompilations.  Executables are lowered under the core's kernel
    backend (``kernels=``, the ``--kernels`` flag): Pallas flash/AAQ
    kernels or the XLA refs — each served batch records which backend it
    ran;
  * the AAQ-aware admission controller (repro.serving.admission) pricing
    every (bucket, batch) candidate in peak activation bytes — *per device*
    when the bucket is mesh-sharded;
  * a device-mesh placement layer (repro.serving.placement): with
    ``mesh=``/``shard_threshold=`` set, buckets at/above the threshold are
    lowered under the mesh with the pair representation sharded over the
    model axis (``ppm_serving_rules``), smaller buckets stay single-device.
    The placement label is part of the executable-cache key (zero steady-
    state recompiles still holds) and is stamped on every ``FoldResult``.

Numerics contract: padding is non-rescaling masking end to end (see
``ppm_forward``), so a request served from a padded batch yields coords
bitwise identical to the same request padded to the same bucket at batch 1
— which is exactly what the fixed sequential fallback computes, and why the
client/legacy paths agree bitwise however their batches are composed OR
pipelined (in-flight depth changes overlap, never inputs).  Fidelity
(``tm_vs_fp``) re-runs each batch through the cached FP16-baseline
executable of the same (bucket, launch size) and TM-scores real-token
coords per request.

Telemetry accounting: ``batch_start`` (the end of queue wait) is stamped
AFTER the executable is resolved, so a cold bucket's multi-second compile
lands in ``queue_wait_ms`` (the request really was waiting on it) and in
its own ``compile_ms`` column — never in ``run_ms``, whose p95/p99
percentiles stay clean on cold starts.  ``run_ms`` is launch-to-ready
device wall time; with ``inflight_depth > 1`` it includes time queued
behind the previous in-flight batch, and in a cold window it can span a
NEIGHBOR batch's host-side compile (the device computes on while the host
compiles, so launch-to-ready is still the honest measure; each batch's
own compile is always isolated in its own ``compile_ms``).  The batch's
own device time is ``device_ms``: from the later of its launch and the
previous batch's ready time to its own ready time (the ``device`` span on
its trace track), an upper bound when the host came to ``block`` after the
device had finished.  With fidelity on, batch k's FP re-run runs after
batch k is ready and counts in batch k+1's ``device_ms``.

Clock: ``clock`` (default ``time.monotonic``) stamps batch starts on the
same monotonic clock the client stamps arrivals/deadlines with, so
queue_wait_ms can never go negative under NTP adjustment; perf_counter is
used only for *durations* (compile/run).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.schemes import FP16Baseline, QuantScheme, make_scheme
from repro.kernels import dispatch
from repro.serving.costmodel import CostModel
from repro.serving.longfold import ChunkPolicy
from repro.serving.metrics import reset_compile_watch
from repro.serving.observability.tracing import (PROC_ENGINE, Span, Tracer,
                                                 gc_pause_s)
from repro.serving.placement import (PlacementPolicy, lower_sharded,
                                     place_inputs)
from repro.serving.scheduler import ScheduledBatch, static_batch_for
from repro.serving.types import FoldResult
from repro.serving.workload import FoldWorkload, Workload


class BatchExecutionError(RuntimeError):
    """Raised by ``retire()``/``execute()`` when a launched batch fails;
    carries the ``ScheduledBatch`` so the pump can terminate its handles
    (FAILED results) instead of stranding them RUNNING forever."""

    def __init__(self, batch: ScheduledBatch, cause: BaseException):
        super().__init__(f"batch execution failed: {cause!r}")
        self.batch = batch
        self.cause = cause


@dataclasses.dataclass
class InFlightBatch:
    """One dispatched-but-not-retired batch riding the in-flight ring."""
    batch: ScheduledBatch
    bucket: int
    launched_b: int                    # rows the executable runs
    placement: Any
    chunk_size: int                    # 0 = unchunked trunk
    out: dict                          # device outputs (unblocked futures)
    fp_out: dict | None                # async fidelity re-run (or None)
    compile_s: float
    batch_start: float                 # core clock, post-executable-resolve
    t_launch: float                    # perf_counter at launch (run_ms t0)
    est: int                           # admission price at launched_b
    backend: str                       # dispatch label
    occupancy: float                   # real tokens / (launched_b * bucket)
    # tracing (defaulted: nothing outside the core constructs these, but
    # tests monkeypatch dispatch with stubs that skip them)
    seq: int = 0                       # monotone batch sequence number
    thread: str = ""                   # trace track, "batch-NNNN"
    launched_at: float = 0.0           # tracer clock at launch
    device_s: float = 0.0              # queue-free device time (at retire)


class EngineCore:
    def __init__(self, params, cfg, scheme: QuantScheme | str | None = None, *,
                 buckets: tuple[int, ...] | None = None,
                 max_tokens_per_batch: int = 1024, max_batch: int = 8,
                 mem_budget_mb: float | None = None,
                 fidelity: bool = False, kernels: str = dispatch.AUTO,
                 keep_distogram: bool = True,
                 mesh=None, shard_threshold: int | None = None,
                 chunk_size: int | str | None = None,
                 inflight_depth: int = 2,
                 clock: Callable[[], float] = time.monotonic,
                 tracer: Tracer | None = None,
                 workload: Workload | None = None,
                 cost_model: CostModel | None = None):
        from repro.serving.scheduler import pow2_buckets
        if inflight_depth < 1:
            raise ValueError(f"inflight_depth must be >= 1, "
                             f"got {inflight_depth}")
        self.params = params
        self.cfg = cfg
        if scheme is None:
            scheme = FP16Baseline()
        elif isinstance(scheme, str):
            scheme = make_scheme(scheme)
        self.scheme = scheme
        self.buckets = tuple(sorted(buckets or pow2_buckets(16, 512)))
        self.max_tokens_per_batch = max_tokens_per_batch
        self.max_batch = max_batch
        self.fidelity = fidelity
        self.keep_distogram = keep_distogram
        self.clock = clock
        if kernels not in dispatch.BACKENDS:
            raise ValueError(f"kernels must be one of {dispatch.BACKENDS}, "
                             f"got {kernels!r}")
        self.kernels = kernels
        self.placement = PlacementPolicy(mesh=mesh,
                                         shard_threshold=shard_threshold)
        budget = None if mem_budget_mb is None else int(mem_budget_mb * 1e6)
        # the workload plugin owns everything model-specific: the traced
        # forward + input specs, host padding, the admission cost model,
        # and retire-side transfer/result construction
        self.workload = (FoldWorkload() if workload is None
                         else workload).bind(self)
        self.admission = self.workload.make_admission(budget)
        # the long-fold planner: decides per bucket whether the trunk runs
        # row-chunked and at what size, priced against this same admission
        # controller — and wires itself back in so every admission estimate
        # for a chunked bucket uses the chunked-path cost model
        self.chunk = ChunkPolicy(chunk_size, admission=self.admission)
        self.admission.chunk_for = self.chunk.chunk_for
        self.inflight_depth = inflight_depth
        self._inflight: deque[InFlightBatch] = deque()
        self.metrics = self.workload.make_metrics()
        # span tracer shares the engine clock so batch spans line up with
        # request timestamps; the client re-exports it as ``client.tracer``
        self.tracer = tracer if tracer is not None else Tracer(clock=clock)
        self._batch_seq = 0
        # tracer clock at which the last retired batch's outputs were
        # ready: the device starts the next batch no earlier
        self._ready_at = float("-inf")
        # every admission verdict (probes included) feeds the metrics
        # registry; late-bound through self.metrics because run() swaps the
        # metrics object per trace
        self.admission.on_decision = (
            lambda d, ns, b: self.metrics.record_admission(
                d.verdict, ns, estimator=d.estimator))
        # a fresh engine starts a fresh compile-watch epoch: watchers marked
        # during a PREVIOUS engine's lifetime can't count its compiles here
        reset_compile_watch()
        self._fp_scheme = FP16Baseline()
        # key: (bucket, launch_batch, scheme.name, placement.label, chunk)
        self._executables: dict[tuple[int, int, str, str, int], object] = {}
        self._placed_params: dict[str, object] = {}
        self._compile_count = 0
        # measured per-executable latencies: the table every priced decision
        # (launch-size reuse, deadline feasibility, adaptive linger) reads;
        # pre-loaded from ``--cost-table`` or calibrated in place, refined
        # online by every retire()
        self.cost_model = (CostModel() if cost_model is None
                           else cost_model).bind(self)
        # admission explain() surfaces measured predicted latency next to
        # its memory breakdown
        self.admission.cost_model = self.cost_model

    # -- shape policy -----------------------------------------------------
    def bucket_for(self, length: int) -> int | None:
        """Smallest bucket edge holding ``length`` (None = too long)."""
        from repro.serving.scheduler import bucket_for
        return bucket_for(self.buckets, length)

    def batch_for_bucket(self, bucket: int) -> int:
        """The MAX batch size this bucket may launch at (the launch-size
        cap; actual launches fit the batch's occupancy, see
        ``launch_size_for``)."""
        return static_batch_for(bucket, self.max_tokens_per_batch,
                                self.max_batch, self.admission)

    def launch_size_for(self, bucket: int, n: int, scheme: QuantScheme,
                        placement) -> int:
        """Occupancy-fitted launch size for ``n`` real rows: the exact
        count, unless a slightly larger executable is already cached for
        this (bucket, scheme, placement) and reusing it is cheaper than
        compiling the exact size.  With a calibrated cost model the choice
        is priced in measured milliseconds — predicted dummy-row burn
        (``(b - n) * marginal_row_ms``) against the measured compile cost
        for this bucket's executables; without one it falls back to the
        static waste guard (at most ``max(1, n // 2)`` dummy rows).

        Deterministic given the trace: calibrated entries are FROZEN at
        calibration (live EWMA drift never feeds this), so depth-1 and
        pipelined runs — and a restart reloading the same persisted table —
        launch identical shapes."""
        cap = self.batch_for_bucket(bucket)
        n = min(n, cap)
        chunk = self.chunk.chunk_for(bucket) or 0
        cached = sorted(b for (bk, b, sn, pl, ck) in self._executables
                        if bk == bucket and sn == scheme.name
                        and pl == placement.label and ck == chunk
                        and b >= n)
        marginal = self.cost_model.marginal_row_ms(bucket,
                                                   calibrated_only=True)
        compile_ms = self.cost_model.compile_ms_for(bucket)
        for b in cached:
            if marginal is not None and compile_ms is not None:
                if (b - n) * marginal <= compile_ms:
                    return b
            elif b - n <= max(1, n // 2):
                return b
        return n

    # -- executable cache -------------------------------------------------
    @property
    def compile_count(self) -> int:
        return self._compile_count

    def _executable(self, bucket: int, batch: int, scheme: QuantScheme):
        """AOT-compiled forward for (bucket, batch, scheme, placement);
        cached, counted.

        Lowered under the core's kernel backend, so a ``kernels='pallas'``
        engine bakes the Pallas flash/AAQ kernels into every bucketed
        executable (interpret mode off-TPU).  The placement label is part
        of the cache key: routing a bucket to the mesh is a distinct
        executable, and repeated batches of the same (bucket, batch,
        scheme, placement) never recompile.  So is the chunk the long-fold
        planner picked for this bucket — the chunk plan is a function of
        the bucket alone, so steady-state chunked serving also performs
        zero recompilations.
        """
        placement = self.placement.placement_for(bucket)
        chunk = self.chunk.chunk_for(bucket) or 0
        key = (bucket, batch, scheme.name, placement.label, chunk)
        if key in self._executables:
            return self._executables[key], 0.0
        specs = self.workload.input_specs(bucket, batch)
        t0 = time.perf_counter()
        with dispatch.use_backend(self.kernels):
            fwd = partial(self.workload.forward, scheme, chunk)
            if placement.sharded:
                compiled = lower_sharded(placement, fwd, self.params,
                                         *specs)
            else:
                compiled = jax.jit(fwd).lower(self.params,
                                              *specs).compile()
        compile_s = time.perf_counter() - t0
        self._executables[key] = compiled
        self._compile_count += 1
        self.metrics.record_compile(bucket, compile_s * 1e3,
                                    scheme=scheme.name,
                                    placement=placement.label)
        # every cache miss prices future occupancy-vs-recompile choices
        self.cost_model.record_compile(key, compile_s * 1e3)
        return compiled, compile_s

    def _params_for(self, placement):
        """Call-time params matching the placement's lowered shardings
        (mesh-replicated copies are cached per placement label)."""
        if not placement.sharded:
            return self.params
        if placement.label not in self._placed_params:
            [placed] = place_inputs(placement, self.params)
            self._placed_params[placement.label] = placed
        return self._placed_params[placement.label]

    def _forward(self, scheme, chunk, params, *inputs):
        """Back-compat alias for the workload's traced forward."""
        return self.workload.forward(scheme, chunk, params, *inputs)

    def warmup(self, ladder: tuple[int, ...] | None = None) -> None:
        """Pre-compile a size LADDER of (bucket, launch_batch) executables
        (and their FP twins if fidelity is on): by default {1, cap//2, cap}
        per bucket — the saturated shape, the half-full shape batches decay
        through as traffic drains, and the solo shape a lone long request
        launches at.  With the cap-only warmup this engine used to have,
        that first solo request ate a cold multi-second compile in
        queue_wait; now it hits the cache.  Chunked buckets warm their
        chunked executables automatically (the chunk plan is consulted
        inside ``_executable``).  Occupancy-fitted sizes off the ladder
        still compile on their first appearance (each once; the waste guard
        reuses nearby cached sizes for trailing batches)."""
        for bucket in self.buckets:
            cap = self.batch_for_bucket(bucket)
            if cap < 1:
                continue                    # bucket over budget even solo
            sizes = ({1, max(1, cap // 2), cap} if ladder is None
                     else {min(cap, max(1, s)) for s in ladder})
            for b in sorted(sizes):
                self._executable(bucket, b, self.scheme)
                if self.fidelity:
                    self._executable(bucket, b, self._fp_scheme)

    def warmup_from_table(self) -> int:
        """Pre-compile every cost-table key matching this engine's current
        context (scheme — plus the FP twin when fidelity is on — placement
        label, chunk plan, within bucket caps).  A restart reloading a
        persisted table warms the previous run's WHOLE executable set, not
        just the static ladder, so steady-state serving performs zero
        compiles from the first batch.  Returns the number of table keys
        warmed."""
        want = {self.scheme.name: self.scheme}
        if self.fidelity:
            want[self._fp_scheme.name] = self._fp_scheme
        buckets = set(self.buckets)
        warmed = 0
        for key in sorted(self.cost_model.entries, key=str):
            bucket, b, scheme_name, label, chunk = key
            if bucket not in buckets or scheme_name not in want:
                continue
            placement = self.placement.placement_for(bucket)
            if (label != placement.label
                    or chunk != (self.chunk.chunk_for(bucket) or 0)):
                continue
            if not 1 <= b <= self.batch_for_bucket(bucket):
                continue
            self._executable(bucket, b, want[scheme_name])
            warmed += 1
        return warmed

    # -- pipelined execution ----------------------------------------------
    @property
    def inflight_count(self) -> int:
        return len(self._inflight)

    @property
    def inflight_full(self) -> bool:
        return len(self._inflight) >= self.inflight_depth

    def dispatch(self, batch: ScheduledBatch) -> InFlightBatch:
        """Stage 1: resolve executables, pad, device-put, LAUNCH — without
        blocking on the result.  Raises RuntimeError when the in-flight
        ring is full (``retire()`` first) and propagates compile/launch
        errors to the caller (the pump turns them into FAILED results).
        """
        if self.inflight_full:
            raise RuntimeError(
                f"in-flight ring full ({self.inflight_depth}); retire() "
                f"the oldest batch before dispatching another")
        bucket = batch.bucket
        seq = self._batch_seq
        self._batch_seq += 1
        thread = f"batch-{seq:04d}"      # one trace track per batch: the
        # dispatch/device/retire chain of batch k+1 visibly overlaps
        # batch k's track in the exported Perfetto timeline
        tr = self.tracer
        d_span = tr.begin("dispatch", process=PROC_ENGINE, thread=thread,
                          batch_seq=seq, bucket=bucket,
                          batch_size=len(batch.requests),
                          scheme=self.scheme.name,
                          requests=[r.request_id for r in batch.requests],
                          gc_s=gc_pause_s())
        placement = self.placement.placement_for(bucket)
        try:
            with tr.annotate(f"serve.dispatch/{bucket}", seq):
                launched_b = self.launch_size_for(
                    bucket, len(batch.requests), self.scheme, placement)
                with self._phase("resolve_executable", d_span) as rs:
                    compiled, compile_s = self._executable(
                        bucket, launched_b, self.scheme)
                    fp_exec = None
                    if (self.fidelity
                            and self.scheme.name != self._fp_scheme.name):
                        fp_exec, fp_compile_s = self._executable(
                            bucket, launched_b, self._fp_scheme)
                        compile_s += fp_compile_s
                    rs.attrs["cache"] = "hit" if compile_s == 0.0 else "miss"
                    rs.attrs["compile_s"] = compile_s
                # queue wait ends HERE, after executables resolve: a cold
                # bucket's multi-second compile is queue time for the
                # requests waiting on it (and its own compile_ms column) —
                # never part of run_ms
                batch_start = self.clock()
                with self._phase("pad", d_span):
                    inputs = self.workload.pad_inputs(
                        batch.requests, bucket, launched_b)
                with self._phase("device_put", d_span):
                    inputs_j = tuple(jnp.asarray(a) for a in inputs)
                    params = self._params_for(placement)
                    if placement.sharded:
                        # AOT executables demand inputs matching their
                        # lowered shardings
                        inputs_j = place_inputs(placement, *inputs_j)
                real_tokens = sum(r.length for r in batch.requests)
                with self._phase("launch", d_span) as launch:
                    t_launch = time.perf_counter()
                    out = compiled(params, *inputs_j)  # async: no block
                    # the fidelity re-run launches behind the main forward
                    # on the same device stream — it overlaps host-side work
                    # instead of waiting for the main batch's transfer like
                    # the synchronous path used to
                    fp_out = (None if fp_exec is None
                              else fp_exec(params, *inputs_j))
        except Exception as e:
            tr.end(d_span, status="failed", error=repr(e))
            raise
        chunk = self.chunk.chunk_for(bucket) or 0
        tr.end(d_span, launch_batch=launched_b,
               occupancy=real_tokens / (launched_b * bucket),
               placement=placement.label, chunk_size=chunk)
        flight = InFlightBatch(
            batch=batch, bucket=bucket, launched_b=launched_b,
            placement=placement, chunk_size=chunk, out=out, fp_out=fp_out,
            compile_s=compile_s, batch_start=batch_start,
            t_launch=t_launch,
            est=self.admission.estimate_bytes(bucket, launched_b),
            backend=dispatch.describe(
                self.kernels, seq=bucket,
                # both auto-mode floors, at the pair-dataflow token count
                # the launched executable actually flattens
                qmm_tokens=launched_b * bucket * bucket),
            occupancy=real_tokens / (launched_b * bucket),
            seq=seq, thread=thread, launched_at=launch.t_start)
        self._inflight.append(flight)
        self.metrics.record_dispatch(len(self._inflight),
                                     self.inflight_depth, flight.occupancy,
                                     bucket=bucket, scheme=self.scheme.name,
                                     placement=placement.label)
        return flight

    def retire(self) -> list[FoldResult]:
        """Stage 2: block on the OLDEST in-flight batch, one host transfer
        of its coords, lazy distogram handles, fidelity TM scores, and
        FoldResults (recorded in metrics).  Returns [] when nothing is in
        flight; raises ``BatchExecutionError`` (carrying the batch) when
        the launched computation fails.
        """
        if not self._inflight:
            return []
        flight = self._inflight.popleft()
        batch = flight.batch
        tr = self.tracer
        r_span = tr.begin("retire", process=PROC_ENGINE,
                          thread=flight.thread or f"batch-{flight.seq:04d}",
                          batch_seq=flight.seq, bucket=flight.bucket)
        try:
            with tr.annotate(f"serve.retire/{flight.bucket}", flight.seq):
                with self._phase("block", r_span) as block:
                    self.workload.block_on(flight.out)
                run_s = time.perf_counter() - flight.t_launch
                self._record_device(flight, block)
                with self._phase("transfer", r_span):
                    # the workload owns the device->host move and any
                    # lazy-transfer policy (fold defers the distogram —
                    # the peak host-memory term at long N — behind a
                    # shared BatchDeviceOutput)
                    payload = self.workload.transfer(flight)
        except Exception as e:
            tr.end(r_span, status="failed", error=repr(e), gc_s=gc_pause_s())
            raise BatchExecutionError(batch, e) from e
        tr.end(r_span, gc_s=gc_pause_s())
        self.metrics.record_inflight(len(self._inflight))
        # live refinement: predict BEFORE observing (the EWMA would
        # otherwise be pulled toward the value it is judged against), then
        # feed this batch's measured launch-to-ready latency back in
        actual_ms = run_s * 1e3
        predicted_ms = self.cost_model.predict_run_ms(flight.bucket,
                                                      flight.launched_b)
        if predicted_ms is not None:
            self.metrics.record_prediction(predicted_ms, actual_ms)
        self.cost_model.observe(
            (flight.bucket, flight.launched_b, self.scheme.name,
             flight.placement.label, flight.chunk_size), actual_ms)
        self.metrics.record_cost_table(self.cost_model.entry_count,
                                       self.cost_model.calibrated_count,
                                       self.cost_model.age_s())
        results = self.workload.build_results(flight, run_s, payload)
        for r in results:
            self.metrics.record(r)
        return results

    def _phase(self, name: str, parent: Span):
        """A span of one host phase of a batch, on the batch's track under
        ``parent``, with the profiler annotation ``serve.<name>/<bucket>``."""
        return self.tracer.span(
            name, process=PROC_ENGINE, thread=parent.thread, parent=parent,
            annotation=f"serve.{name}/{parent.attrs['bucket']}")

    def _record_device(self, flight: InFlightBatch, block: Span) -> None:
        """The batch's ``device`` span: from the later of its launch and
        the previous batch's ready time to its own ready time, when
        ``block`` returned.  ``late`` marks a ``block`` under 1 ms: the host
        came after the device had finished, so the end is an upper bound."""
        ready = block.t_end
        start = min(max(flight.launched_at, self._ready_at), ready)
        flight.device_s = ready - start
        self._ready_at = ready
        tr = self.tracer
        late = {"late": True} if block.duration < 1e-3 else {}
        span = tr.begin("device", process=PROC_ENGINE, thread=block.thread,
                        t=start, batch_seq=flight.seq, bucket=flight.bucket)
        tr.end(span, t=ready, **late)

    def execute(self, batch: ScheduledBatch) -> list[FoldResult]:
        """Synchronous compat surface: dispatch + immediately retire.
        Requires an empty in-flight ring (it would otherwise retire an
        OLDER batch's results as this one's)."""
        if self._inflight:
            raise RuntimeError(
                "execute() needs an empty in-flight ring; use "
                "dispatch()/retire() when pipelining")
        self.dispatch(batch)
        return self.retire()


class FoldEngine:
    """Legacy blocking surface: ``submit() -> int`` / ``step()`` / ``run()``.

    A thin compatibility wrapper over ``FoldClient`` — every request goes
    through the same client pump (default priority, no deadline), so the
    two surfaces are one code path and produce identical results.  New code
    should use ``repro.serving.client.FoldClient`` directly for handles,
    priorities, deadlines, cancellation, and progress events.
    """

    def __init__(self, params, cfg, scheme: QuantScheme | str | None = None, *,
                 buckets: tuple[int, ...] | None = None,
                 max_tokens_per_batch: int = 1024, max_batch: int = 8,
                 mem_budget_mb: float | None = None,
                 fidelity: bool = False, kernels: str = dispatch.AUTO,
                 keep_distogram: bool = True,
                 mesh=None, shard_threshold: int | None = None,
                 chunk_size: int | str | None = None,
                 inflight_depth: int = 2, linger_ms: float = 0.0,
                 clock: Callable[[], float] = time.monotonic):
        from repro.serving.client import FoldClient
        self.client = FoldClient(
            params, cfg, scheme, buckets=buckets,
            max_tokens_per_batch=max_tokens_per_batch, max_batch=max_batch,
            mem_budget_mb=mem_budget_mb, fidelity=fidelity, kernels=kernels,
            keep_distogram=keep_distogram, mesh=mesh,
            shard_threshold=shard_threshold, chunk_size=chunk_size,
            inflight_depth=inflight_depth,
            linger_ms=linger_ms, clock=clock)
        self.core = self.client.core

    # -- delegated state ---------------------------------------------------
    params = property(lambda self: self.core.params)
    cfg = property(lambda self: self.core.cfg)
    scheme = property(lambda self: self.core.scheme)
    buckets = property(lambda self: self.core.buckets)
    kernels = property(lambda self: self.core.kernels)
    fidelity = property(lambda self: self.core.fidelity)
    admission = property(lambda self: self.core.admission)
    placement = property(lambda self: self.core.placement)
    chunk = property(lambda self: self.core.chunk)
    scheduler = property(lambda self: self.client.scheduler)
    metrics = property(lambda self: self.core.metrics)
    compile_count = property(lambda self: self.core.compile_count)

    def bucket_for(self, length: int) -> int | None:
        return self.core.bucket_for(length)

    def batch_for_bucket(self, bucket: int) -> int:
        return self.core.batch_for_bucket(bucket)

    def warmup(self, ladder: tuple[int, ...] | None = None) -> None:
        self.core.warmup(ladder)

    # -- legacy request lifecycle -----------------------------------------
    def submit(self, seq) -> int:
        """Queue a sequence (or FoldRequest); returns its request id."""
        return self.client.submit(seq).request_id

    def step(self) -> list[FoldResult]:
        """Serve the next scheduled batch; [] when the queue is empty."""
        return self.client.drive(max_batches=1)

    def drain(self) -> list[FoldResult]:
        return self.client.drive()

    def run(self, seqs, *, reset_metrics: bool = True) -> list[FoldResult]:
        """Submit a trace, drain it, return results in request order."""
        return self.client.run(seqs, reset_metrics=reset_metrics)
