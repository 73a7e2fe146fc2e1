"""The ``Workload`` protocol: what a model family must provide to be
served by the substrate.

PR 1-8 built the serving stack — bucketed AOT compilation, token-budget
scheduling, admission control, the handle/event lifecycle, span tracing,
metrics, HTTP transport — hard-wired to protein folding.  This module
extracts the fold-specific pieces behind a small interface so the same
substrate hosts other model families (the first second tenant is
AAQ-quantized-KV LM decode, ``repro.serving.lm``).

A workload owns exactly the five things that differ between model
families; everything else (queues, priorities, deadlines, cancellation,
events, tracing, metrics plumbing, transport) is substrate:

  * **executable surface** — ``input_specs`` (the ShapeDtypeStructs a
    bucketed executable is lowered against) and ``forward`` (the traced
    function).  The host engine owns the cache and its key; the workload
    defines what gets compiled.
  * **batch formation** — ``pad_inputs`` turns a picked request list into
    the host arrays the executable consumes (right-padding to the bucket
    edge for folding; slot packing for decode).
  * **admission cost model** — ``make_admission`` prices candidates in the
    workload's own currency (peak activation bytes for folding; KV-cache
    bytes at the scheme's bits-per-value for decode).
  * **retire hooks** — ``block_on`` (which output to synchronize on),
    ``transfer`` (the device->host move, including any lazy-transfer
    policy), ``build_results`` (per-request result objects).
  * **result/event types** — ``result_type`` plus any event kinds beyond
    the shared lifecycle vocabulary (``extra_event_kinds``; LM decode adds
    ``TOKEN``).

``FoldWorkload`` below is the existing fold path moved here VERBATIM from
``EngineCore`` — same ppm_forward closure, same pad/transfer/result code —
so results, CSV/JSON reports, Prometheus series, and span trees are
bitwise-identical to the pre-refactor engine.  ``EngineCore`` constructs
one by default; nothing changes for existing callers.

Execution shape note: bucketed folding runs request-per-batch (dispatch a
padded batch, retire it once); autoregressive decode runs request-per-
*slot* across many steps (sequences join and retire from the running batch
each step).  The protocol deliberately does not fix the pump shape — the
fold workload is hosted by ``EngineCore``'s dispatch/retire ring, the LM
workload by ``LMEngineCore``'s step loop — but both speak the same
admission/result/event contracts, so client, fleet router, and HTTP
transport code is shared unchanged.
"""
from __future__ import annotations

from functools import partial
from typing import TYPE_CHECKING, Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.ppm import ppm_forward, tm_score
from repro.models.ppm.trunk import CHUNKED_ATTN_LEN
from repro.serving.admission import AdmissionController
from repro.serving.metrics import EngineMetrics
from repro.serving.observability.tracing import PROC_ENGINE
from repro.serving.types import (BatchDeviceOutput, FoldResult,
                                 LazyDistogram, pad_to_bucket)

if TYPE_CHECKING:                      # pragma: no cover - typing only
    from repro.serving.engine import InFlightBatch
    from repro.serving.types import FoldRequest


class Workload:
    """Interface a model family implements to be served by the substrate.

    Instances are bound to their host engine with ``bind(core)`` before
    use — hooks read model config, scheme, metrics, and policy objects
    through ``self.core`` so one workload class serves any engine
    configuration.
    """

    #: short label: metrics ``workload=`` label values, trace metadata,
    #: and the ``/v1/fleet`` topology description
    name = "workload"
    #: the per-request result dataclass this workload produces
    result_type: type = FoldResult
    #: event kinds beyond the shared lifecycle vocabulary (must already be
    #: registered in ``repro.serving.events.EVENT_KINDS``)
    extra_event_kinds: tuple[str, ...] = ()

    def __init__(self):
        self.core: Any = None

    def bind(self, core) -> "Workload":
        """Attach the host engine; returns self (chainable in ctors)."""
        self.core = core
        return self

    # -- executable surface -------------------------------------------------
    def input_specs(self, bucket: int, batch: int) -> tuple:
        """ShapeDtypeStructs the (bucket, batch) executable is lowered
        against, in ``forward``'s input order (after params)."""
        raise NotImplementedError

    def forward(self, scheme, chunk, params, *inputs):
        """The traced computation for one batch step.  ``scheme``/``chunk``
        are closure arguments baked into the executable (part of the host
        engine's cache key), ``params``+``inputs`` are call-time arrays."""
        raise NotImplementedError

    # -- batch formation ------------------------------------------------------
    def pad_inputs(self, requests: tuple, bucket: int,
                   launched_b: int) -> tuple:
        """Host arrays for the executable's inputs, padded to the launch
        shape (dummy rows must be finite-garbage-safe)."""
        raise NotImplementedError

    # -- admission cost model -------------------------------------------------
    def make_admission(self, mem_budget_bytes: int | None):
        """The admission controller pricing this workload's candidates
        against the engine's memory budget."""
        raise NotImplementedError

    # -- telemetry ---------------------------------------------------------------
    def make_metrics(self):
        """The metrics object the host engine records into.  The default
        is the fold stack's ``EngineMetrics`` (unlabeled ``fold_*`` series
        — exposition stays byte-identical for existing scrapes); other
        workloads return their own (e.g. ``lm_*`` series const-labeled
        ``workload="lm"``)."""
        return EngineMetrics()

    # -- retire hooks ----------------------------------------------------------
    def block_on(self, out) -> None:
        """Synchronize on the launched output (ends run_ms timing)."""
        raise NotImplementedError

    def transfer(self, flight: "InFlightBatch"):
        """Device->host transfer of the retired batch; returns an opaque
        payload handed to ``build_results``.  Lazy-transfer policies
        (fold's deferred distogram) live here."""
        raise NotImplementedError

    def build_results(self, flight: "InFlightBatch", run_s: float,
                      payload) -> list:
        """Per-request results (``result_type``) for a retired batch, in
        batch-request order, telemetry columns included."""
        raise NotImplementedError

    def describe(self) -> dict:
        return {"workload": self.name}


class FoldWorkload(Workload):
    """The protein-folding path — the code ``EngineCore`` inlined before
    this refactor, moved verbatim (see the bitwise-identity contract in
    the module docstring)."""

    name = "fold"
    result_type = FoldResult

    # -- executable surface -------------------------------------------------
    def input_specs(self, bucket: int, batch: int) -> tuple:
        return (jax.ShapeDtypeStruct((batch, bucket), jnp.int32),
                jax.ShapeDtypeStruct((batch, bucket), jnp.bool_))

    def forward(self, scheme, chunk, params, aatype, mask):
        return ppm_forward(params, aatype, self.core.cfg, scheme, mask=mask,
                           chunk_size=chunk or None)

    # -- batch formation ------------------------------------------------------
    def pad_inputs(self, requests: tuple, bucket: int,
                   launched_b: int) -> tuple:
        return pad_to_bucket([r.aatype for r in requests], bucket,
                             launched_b)

    # -- admission cost model -------------------------------------------------
    def make_admission(self, mem_budget_bytes: int | None
                       ) -> AdmissionController:
        # pricing switches to the chunked score-slab model at the model's
        # token-wise MHA threshold; per-device under sharded placements
        # (mem_budget_mb is a per-device budget)
        return AdmissionController(
            self.core.cfg, self.core.scheme, mem_budget_bytes,
            chunked_len=CHUNKED_ATTN_LEN,
            shards_for=self.core.placement.shards_for)

    # -- retire hooks ----------------------------------------------------------
    def block_on(self, out) -> None:
        jax.block_until_ready(out["coords"])

    def transfer(self, flight: "InFlightBatch"):
        # one device->host transfer per batch for coords; numpy slicing
        # after that (a device-array slice would eagerly compile per
        # distinct length and break the zero-recompile steady state).  The
        # distogram — the peak host-memory term at long N — stays on device
        # behind a shared BatchDeviceOutput until a consumer asks a
        # LazyDistogram for it.
        core = self.core
        coords_host = np.asarray(flight.out["coords"])
        disto = None
        if core.keep_distogram:
            darr = flight.out["distogram"]
            pinned = int(getattr(darr, "nbytes", 0))
            core.metrics.record_pinned(pinned)
            metrics = core.metrics   # bind: run() swaps metrics
            disto = BatchDeviceOutput(
                darr, nbytes=pinned,
                on_release=(lambda m=metrics, n=pinned:
                            m.record_pinned(-n)),
                span=partial(core.tracer.span, "fetch",
                             process=PROC_ENGINE, thread=flight.thread,
                             annotation=f"serve.fetch/{flight.bucket}",
                             batch_seq=flight.seq, bucket=flight.bucket,
                             bytes=pinned))
        fp_coords = (None if flight.fp_out is None
                     else np.asarray(flight.fp_out["coords"]))
        return coords_host, disto, fp_coords

    def build_results(self, flight: "InFlightBatch", run_s: float,
                      payload) -> list[FoldResult]:
        coords_host, disto, fp_coords = payload
        core = self.core
        batch = flight.batch
        results = []
        for row, req in enumerate(batch.requests):
            coords = np.array(coords_host[row, :req.length])
            tm = None
            if core.fidelity:
                tm = 1.0 if fp_coords is None else float(tm_score(
                    jnp.asarray(coords),
                    jnp.asarray(fp_coords[row, :req.length])))
            results.append(FoldResult(
                request_id=req.request_id, length=req.length,
                bucket=flight.bucket, batch_size=len(batch.requests),
                coords=coords,
                distogram=None if disto is None else LazyDistogram(
                    disto, row, req.length,
                    int(flight.out["distogram"].shape[-1])),
                tm_vs_fp=tm,
                priority=req.priority,
                queue_wait_ms=(flight.batch_start - req.arrival_time) * 1e3,
                compile_ms=flight.compile_s * 1e3,
                run_ms=run_s * 1e3,
                device_ms=flight.device_s * 1e3,
                launched_batch=flight.launched_b,
                occupancy=flight.occupancy,
                est_activation_bytes=flight.est,
                kernel_backend=flight.backend,
                placement=flight.placement.label,
                chunk_size=flight.chunk_size))
        return results
