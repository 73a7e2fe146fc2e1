"""Observability-layer tests: span-tree completeness and terminal closure
over the request lifecycle, Chrome-trace/Perfetto export validity, the
pipeline-overlap invariant at depth 2, metrics-registry thread safety and
Prometheus exposition, the bounded driver-error ring, the compile-watch
epoch, pinned-distogram accounting, and bench provenance; the per-batch
device, fetch and gc spans and the profiler annotations' join stats.
"""
import gc
import glob
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduce_ppm_config
from repro.core import make_scheme
from repro.models.ppm import init_ppm
from repro.serving import (CompileWatcher, EngineCore, FoldClient,
                           FoldRequest, FoldWorkload, MetricsRegistry,
                           MetricsServer, ScheduledBatch,
                           pipeline_overlaps, reset_compile_watch,
                           validate_chrome_trace)
from repro.serving import metrics as metrics_mod
from repro.serving.observability.tracing import (GC_THREAD, PROC_ENGINE,
                                                 PROC_REQUESTS, Tracer,
                                                 gc_pause_s, span_tree)

CFG = reduce_ppm_config()
PARAMS = init_ppm(jax.random.PRNGKey(0), CFG)
SCHEME = make_scheme("lightnobel_aaq")
RNG = np.random.default_rng(13)


def _seq(length: int) -> np.ndarray:
    return RNG.integers(0, 20, length).astype(np.int32)


class ManualClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _client(**kw) -> FoldClient:
    kw.setdefault("buckets", (32,))
    kw.setdefault("max_tokens_per_batch", 64)
    kw.setdefault("max_batch", 2)
    return FoldClient(PARAMS, CFG, SCHEME, **kw)


# -- span trees: completeness + ordering ------------------------------------
def test_request_span_tree_complete_and_ordered():
    client = _client()
    h = client.submit(_seq(24))
    client.drive()
    assert h.status == "DONE"
    assert sorted(h.spans) == ["admission", "queued", "request", "running"]
    [root] = [t for t in h.span_tree() if t["span"].name == "request"]
    kids = [c["span"].name for c in root["children"]]
    assert kids == ["admission", "queued", "running"]
    # every span closed, children nested in the parent's window, phases in
    # lifecycle order
    spans = {name: s for name, s in h.spans.items()}
    for s in spans.values():
        assert s.t_end is not None, f"span {s.name} never closed"
        assert s.t_end >= s.t_start
    r = spans["request"]
    for child in ("admission", "queued", "running"):
        assert spans[child].t_start >= r.t_start
        assert spans[child].t_end <= r.t_end
    assert spans["admission"].t_start <= spans["queued"].t_start
    assert spans["queued"].t_end <= spans["running"].t_start
    assert r.attrs["status"] == "ok"
    assert r.attrs["request_id"] == h.request_id
    # the running span points at the engine batch that served it
    assert "batch_seq" in spans["running"].attrs


def test_engine_batch_span_tree():
    client = _client()
    for _ in range(2):
        client.submit(_seq(24))
    client.drive()
    tr = client.tracer
    dispatches = tr.find("dispatch", process=PROC_ENGINE)
    assert len(dispatches) == 1          # one bucket-32 batch of 2
    d = dispatches[0]
    children = {s.name for s in tr.find(process=PROC_ENGINE)
                if s.parent_id == d.span_id}
    assert children == {"resolve_executable", "pad", "device_put", "launch"}
    [resolve] = [s for s in tr.find("resolve_executable")
                 if s.parent_id == d.span_id]
    assert resolve.attrs["cache"] == "miss"     # cold bucket compiled
    assert d.attrs["launch_batch"] >= 2
    retires = tr.find("retire", process=PROC_ENGINE)
    assert len(retires) == 1
    rk = {s.name for s in tr.find(process=PROC_ENGINE)
          if s.parent_id == retires[0].span_id}
    assert rk == {"block", "transfer"}
    # device runs on the same track from the launch (no batch before it)
    # to the end of the block, when the outputs were ready
    [launch] = [s for s in tr.find("launch") if s.parent_id == d.span_id]
    [block] = [s for s in tr.find("block")
               if s.parent_id == retires[0].span_id]
    [dev] = tr.find("device", thread=d.thread)
    assert dev.t_start == launch.t_start and dev.t_end == block.t_end
    assert dev.attrs["batch_seq"] == d.attrs["batch_seq"]


# -- device, fetch and gc spans; profiler annotations -----------------------
class _StubFold(FoldWorkload):
    """The fold workload with a forward that compiles in no time; each
    ``block`` takes the next of ``block_s`` on ``clock``."""

    def __init__(self, clock=None, block_s=()):
        super().__init__()
        self.clock = clock
        self.block_s = list(block_s)

    def forward(self, scheme, chunk, params, aatype, mask):
        b, n = aatype.shape
        return {"coords": jnp.broadcast_to(aatype[..., None], (b, n, 3))
                .astype(jnp.float32),
                "distogram": jnp.broadcast_to(mask[:, :, None, None],
                                              (b, n, n, 4))
                .astype(jnp.float32)}

    def block_on(self, out):
        super().block_on(out)
        if self.block_s:
            self.clock.advance(self.block_s.pop(0))


def _stub_core(**kw) -> EngineCore:
    return EngineCore(PARAMS, CFG, SCHEME, buckets=(32,),
                      max_tokens_per_batch=64, max_batch=2,
                      inflight_depth=2, **kw)


def _batch(n_requests: int, first_id: int) -> ScheduledBatch:
    return ScheduledBatch(32, tuple(FoldRequest(first_id + i, _seq(24))
                                    for i in range(n_requests)), 0)


def test_device_span_starts_at_launch_or_previous_ready():
    clock = ManualClock()
    core = _stub_core(clock=clock,
                      workload=_StubFold(clock, block_s=(1.0, 0.8, 0.0)))
    core.dispatch(_batch(1, 0))              # launched at 1000.0
    clock.advance(0.5)
    core.dispatch(_batch(1, 1))              # launched at 1000.5
    [r0] = core.retire()                     # ready at 1001.5
    [r1] = core.retire()                     # ready at 1002.3
    core.dispatch(_batch(1, 2))              # launched at 1002.3
    clock.advance(2.0)                       # the host is away ...
    [r2] = core.retire()                     # ... block returns at once
    devs = sorted(core.tracer.find("device", process=PROC_ENGINE),
                  key=lambda s: s.attrs["batch_seq"])
    assert [(s.t_start, s.t_end) for s in devs] == pytest.approx(
        [(1000.0, 1001.5), (1001.5, 1002.3), (1002.3, 1004.3)])
    # batch 1 waited behind batch 0 from its launch at 1000.5 to 1001.5
    assert [s.attrs.get("late") for s in devs] == [None, None, True]
    assert [d.thread for d in devs] == ["batch-0000", "batch-0001",
                                        "batch-0002"]
    assert [r.device_ms for r in (r0, r1, r2)] == pytest.approx(
        [1500.0, 800.0, 2000.0])
    assert r1.run_ms >= 0.0                  # still perf_counter launch
    hist = core.metrics.registry.get("fold_device_seconds")
    assert hist.count(bucket=32, placement="single",
                      backend=r0.kernel_backend) == 3
    # the export puts the device span, which overlaps dispatch and
    # retire, on a lane of its own; retire keeps its block
    trace = core.tracer.chrome_trace()
    validate_chrome_trace(trace)
    lanes = {e["args"]["name"]: e["tid"] for e in trace["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    on_lane = {e["name"] for e in trace["traceEvents"]
               if e["ph"] == "B" and e["tid"] == lanes["batch-0001.1"]}
    assert on_lane == {"device"}
    block = [e for e in trace["traceEvents"] if e["ph"] in "BE"
             and e["name"] == "block" and e["tid"] == lanes["batch-0001"]]
    assert block[1]["ts"] - block[0]["ts"] == pytest.approx(0.8e6)


def test_fetch_span_once_per_batch_with_bytes():
    core = _stub_core(workload=_StubFold())
    core.dispatch(_batch(2, 0))
    results = core.retire()
    for r in results + results:               # both rows, twice
        np.asarray(r.distogram)
    [fetch] = core.tracer.find("fetch", process=PROC_ENGINE)
    assert fetch.thread == "batch-0000" and fetch.parent_id is None
    assert fetch.attrs["batch_seq"] == 0 and fetch.attrs["bucket"] == 32
    assert fetch.attrs["bytes"] == 2 * 32 * 32 * 4 * 4    # float32
    assert fetch.t_end is not None


def test_gc_collect_counts_and_records_one_gen2_span():
    tr = Tracer()
    gc.disable()                   # no automatic collection in between
    try:
        before = gc_pause_s()
        gc.collect()
        after = gc_pause_s()
    finally:
        gc.enable()
    assert after > before
    [span] = tr.find("gc", process=PROC_ENGINE, thread=GC_THREAD)
    assert span.attrs["generation"] == 2
    assert span.attrs["collected"] >= 0
    assert 0.0 <= span.duration <= after - before + 1e-9
    # a dispatch and a retire stamp the counter
    core = _stub_core(workload=_StubFold())
    core.dispatch(_batch(1, 0))
    core.retire()
    [d] = core.tracer.find("dispatch")
    [r] = core.tracer.find("retire")
    assert after <= d.attrs["gc_s"] <= r.attrs["gc_s"]


def test_gc_spans_under_threads_lose_none():
    """Collections forced from several threads while others record spans:
    the hook never takes a Tracer's lock (a collection can start while a
    thread holds it), and every generation-2 collection becomes one span."""
    import sys
    tr = Tracer()
    seen = []

    def count(phase, info):
        if phase == "stop" and info["generation"] == 2:
            seen.append(1)

    def spans():
        for _ in range(2000):
            tr.end(tr.begin("s", process=PROC_REQUESTS, thread="t",
                            junk=[{} for _ in range(5)]))

    def collect():
        for _ in range(30):
            gc.collect()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    gc.callbacks.append(count)
    try:
        threads = [threading.Thread(target=f)
                   for f in (spans, spans, spans, collect, collect)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        gc.callbacks.remove(count)
        sys.setswitchinterval(interval)
    assert len(tr.find("gc", thread=GC_THREAD)) == len(seen) >= 30


def test_profiler_annotations_carry_batch_seq_and_tracer_clock(tmp_path):
    """Under the JAX profiler on the CPU, the engine's annotations keep
    their plain names and carry ``batch_seq`` and ``t`` (the tracer clock
    at entry): ``start - t`` is one offset for the whole profile."""
    core = _stub_core(workload=_StubFold())
    core.dispatch(_batch(1, 0))        # compile outside the profile
    core.retire()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(3):
            core.dispatch(_batch(1, 1 + i))
            if core.inflight_full:
                core.retire()
        while core.inflight_count:
            for r in core.retire():
                np.asarray(r.distogram)
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    events = [e for plane in data.planes if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("serve.")]
    outer = [e for e in events if e.name in ("serve.dispatch/32",
                                             "serve.retire/32")]
    assert len(outer) == 6
    offsets = []
    for e in events:
        stats = dict(e.stats)
        assert {"batch_seq", "t"} <= set(stats), e.name
        offsets.append(e.start_ns * 1e-9 - stats["t"])
    assert {dict(e.stats)["batch_seq"] for e in outer} == {1, 2, 3}
    assert max(offsets) - min(offsets) < 1e-3
    names = {e.name for e in events}
    assert {"serve.block/32", "serve.launch/32", "serve.fetch/32"} <= names


# -- terminal closure: cancel / expiry / rejection / failure ----------------
def test_terminal_paths_close_spans():
    clock = ManualClock()
    client = _client(clock=clock)
    rej = client.submit(_seq(60))                 # longer than max bucket
    cancelled = client.submit(_seq(24))
    assert cancelled.cancel()
    expiring = client.submit(_seq(24), deadline_s=1.0)
    clock.advance(5.0)
    client.drive()
    assert rej.status == "REJECTED"
    assert expiring.status == "EXPIRED"
    for h, status in ((rej, "rejected"), (cancelled, "cancelled"),
                      (expiring, "expired")):
        root = h.spans["request"]
        assert root.t_end is not None, f"{status} root span left open"
        assert root.attrs["status"] == status
        for s in h.spans.values():
            assert s.t_end is not None
    assert rej.spans["admission"].attrs["verdict"] == "reject"


def test_failed_dispatch_closes_spans_and_terminates():
    client = _client()

    def boom(batch):
        raise RuntimeError("injected dispatch failure")

    client.core.dispatch = boom
    h = client.submit(_seq(24))
    client.drive()
    assert h.result().status == "failed"
    root = h.spans["request"]
    assert root.t_end is not None and root.attrs["status"] == "failed"
    assert h.spans["running"].t_end is not None


# -- chrome trace export ----------------------------------------------------
def test_chrome_trace_schema_and_balance(tmp_path):
    clock = ManualClock()
    client = _client(clock=clock)
    for _ in range(4):
        client.submit(_seq(24))
    client.drive()
    path = str(tmp_path / "trace.json")
    client.save_trace(path)
    with open(path) as fh:
        trace = json.load(fh)
    validate_chrome_trace(trace)          # monotone ts, matched B/E pairs
    events = trace["traceEvents"]
    assert any(e["ph"] == "M" for e in events)
    assert any(e["ph"] == "B" and e["name"] == "dispatch" for e in events)
    assert trace["metadata"]["dropped_spans"] == 0


def test_pipeline_overlap_at_depth_2():
    """The acceptance invariant: with >= 2 batches at inflight depth 2,
    some batch k+1's dispatch span starts before batch k's retire ends —
    the drive loop fills the ring before retiring."""
    client = _client(inflight_depth=2)
    for _ in range(4):                    # 2 batches of 2 at bucket 32
        client.submit(_seq(24))
    client.drive()
    live = pipeline_overlaps(client.tracer)
    assert live >= 1
    # the exported chrome-trace dict (what CI loads from disk) must agree
    exported = json.loads(json.dumps(client.tracer.chrome_trace()))
    assert pipeline_overlaps(exported) == live


def test_no_overlap_at_depth_1():
    client = _client(inflight_depth=1)
    for _ in range(4):
        client.submit(_seq(24))
    client.drive()
    assert pipeline_overlaps(client.tracer) == 0


# -- metrics registry -------------------------------------------------------
def test_registry_thread_safety_exact_totals():
    reg = MetricsRegistry()
    c = reg.counter("hits_total", "hammered", ("worker",))
    g = reg.gauge("depth", "")
    h = reg.histogram("lat_seconds", "", buckets=(0.5, 1.0))
    N, T = 2000, 8

    def hammer(i):
        for _ in range(N):
            c.inc(worker=str(i % 2))
            g.inc()
            h.observe(0.25)
            reg.prometheus_text()         # render concurrently with writes

    threads = [threading.Thread(target=hammer, args=(i,)) for i in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.total() == N * T
    assert g.value() == N * T
    assert h.count() == N * T


def test_prometheus_text_format_and_series():
    client = _client(mem_budget_mb=512.0)
    for _ in range(4):
        client.submit(_seq(24))
    client.drive()
    text = client.metrics_text()
    lines = text.splitlines()
    # exposition grammar: HELP/TYPE headers, then samples
    assert "# TYPE fold_requests_total counter" in lines
    assert "# TYPE fold_queue_depth gauge" in lines
    assert "# TYPE fold_batch_occupancy histogram" in lines
    for series in ("fold_requests_total", "fold_admission_decisions_total",
                   "fold_queue_depth", "fold_inflight_depth",
                   "fold_compiles_total", "fold_batch_occupancy_bucket",
                   "fold_pinned_distogram_bytes", "fold_tokens_total"):
        assert any(l.startswith(series) for l in lines), series
    # labels: bucket on requests, scheme+placement on compiles
    assert any(l.startswith('fold_requests_total{status="ok",bucket="32"}')
               for l in lines)
    assert any('scheme="lightnobel_aaq"' in l and 'placement="single"' in l
               for l in lines if l.startswith("fold_compiles_total"))
    # admission verdicts observed (solo probes + growth probes)
    assert any(l.startswith('fold_admission_decisions_total{verdict="admit"')
               for l in lines)
    # histogram invariants: cumulative buckets, +Inf == _count
    occ = [l for l in lines if l.startswith("fold_batch_occupancy_bucket")]
    inf = [l for l in occ if 'le="+Inf"' in l]
    cnt = [l for l in lines if l.startswith("fold_batch_occupancy_count")]
    assert inf and cnt
    assert inf[0].rsplit(" ", 1)[1] == cnt[0].rsplit(" ", 1)[1]
    # JSON exposition mirrors the same registry
    js = client.metrics_json()
    assert js["fold_requests_total"]["kind"] == "counter"
    assert any(s["labels"]["status"] == "ok"
               for s in js["fold_requests_total"]["series"])


def test_metrics_under_background_driver():
    client = _client()
    client.start()
    try:
        stop = threading.Event()
        texts = []

        def scrape():
            while not stop.is_set():
                texts.append(client.metrics_text())

        t = threading.Thread(target=scrape)
        t.start()
        handles = [client.submit(_seq(24)) for _ in range(6)]
        for h in handles:
            h.result(timeout=600.0)
        stop.set()
        t.join()
    finally:
        client.stop()
    assert all(h.status == "DONE" for h in handles)
    assert texts and all("fold_requests_total" in s for s in texts)
    final = client.metrics_text()
    assert 'fold_requests_total{status="ok",bucket="32"} 6' in final


def test_metrics_server_scrape():
    client = _client()
    client.submit(_seq(24))
    client.drive()
    from urllib.request import urlopen
    with MetricsServer(client, port=0) as srv:
        with urlopen(f"{srv.url}/metrics") as resp:
            body = resp.read().decode()
            assert resp.headers["Content-Type"].startswith("text/plain")
        assert "fold_requests_total" in body
        with urlopen(f"{srv.url}/metrics.json") as resp:
            js = json.load(resp)
        assert js["fold_requests_total"]["kind"] == "counter"
        with urlopen(f"{srv.url}/healthz") as resp:
            hz = json.load(resp)
        assert hz["ok"] is True and hz["driving"] is False


# -- satellite: bounded driver-error ring -----------------------------------
def test_driver_errors_ring_bounded_and_counted():
    client = _client()
    for i in range(40):
        client._record_driver_error(RuntimeError(f"e{i}"))
    assert len(client.driver_errors) == 32
    assert client.driver_errors_dropped == 8
    assert str(client.driver_errors[0]) == "e8"    # oldest evicted first
    text = client.metrics_text()
    assert "fold_driver_errors_total 40" in text
    assert "fold_driver_errors_dropped_total 8" in text


# -- satellite: compile-watch epoch -----------------------------------------
def test_compile_watch_epoch_isolates_engines():
    w = CompileWatcher()
    w.mark()
    # compiles attributed to "engine 1's lifetime"
    metrics_mod._BACKEND_COMPILES += 5
    # standing up a second engine resets the epoch: the watcher must not
    # see engine 1's compiles in its delta anymore
    EngineCore(PARAMS, CFG, SCHEME, buckets=(32,))
    assert w.delta() == 0
    metrics_mod._BACKEND_COMPILES += 2             # post-epoch compiles
    assert w.delta() == 2
    # re-marking re-baselines within the current epoch
    w.mark()
    assert w.delta() == 0


def test_reset_compile_watch_direct():
    w = CompileWatcher()
    metrics_mod._BACKEND_COMPILES += 3
    assert w.delta() == 3
    reset_compile_watch()
    assert w.delta() == 0


# -- pinned distogram accounting --------------------------------------------
def test_pinned_bytes_released_on_fetch():
    client = _client()
    for _ in range(2):
        client.submit(_seq(24))
    results = client.drive()
    pinned = client.metrics.registry.get("fold_pinned_distogram_bytes")
    assert pinned.value() > 0              # batch retired, not yet fetched
    for r in results:
        np.asarray(r.distogram)            # materialize -> release
    assert pinned.value() == 0


# -- satellite: bench provenance --------------------------------------------
def test_bench_provenance_keys():
    from benchmarks.common import provenance
    p = provenance()
    for key in ("git_sha", "jax_version", "jaxlib_version", "backend",
                "device_kind", "platform", "python", "timestamp_utc"):
        assert key in p, key
    assert p["jax_version"] == jax.__version__


# -- tracer unit behavior ---------------------------------------------------
def test_tracer_bounded_and_truncation_marked():
    clock = ManualClock()
    tr = Tracer(clock=clock, max_spans=3)
    spans = [tr.begin(f"s{i}", process=PROC_REQUESTS, thread="t")
             for i in range(5)]
    for s in spans:
        clock.advance(1.0)
        tr.end(s)
    assert len(tr.spans) == 3 and tr.dropped == 2
    trace = tr.chrome_trace()
    validate_chrome_trace(trace)
    assert trace["metadata"]["dropped_spans"] == 2


def test_span_tree_helper_orders_children():
    clock = ManualClock()
    tr = Tracer(clock=clock)
    root = tr.begin("root", process=PROC_REQUESTS, thread="t")
    clock.advance(1.0)
    a = tr.begin("a", process=PROC_REQUESTS, thread="t", parent=root)
    tr.end(a)
    clock.advance(1.0)
    b = tr.begin("b", process=PROC_REQUESTS, thread="t", parent=root)
    tr.end(b)
    tr.end(root)
    [tree] = span_tree(tr.find())
    assert tree["span"] is root
    assert [c["span"].name for c in tree["children"]] == ["a", "b"]
