"""HTTP transport + fleet-router tests: wire-schema round-trips, the
stdlib server over a real socket, telemetry-driven routing, replica-
failure requeue, the lazy-distogram contract across the network, and the
acceptance gate — a 2-replica fleet serving a committed 8-request
mixed-priority trace over HTTP with coords matching the in-process
``FoldClient`` under the fold parity contract (``_parity``).
"""
import json
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest
from _parity import assert_fold_parity

from repro.configs import reduce_ppm_config
from repro.data.pipeline import ProteinSampler
from repro.models.ppm import init_ppm
from repro.serving import (FleetRouter, FoldClient, FoldHTTPServer,
                           MetricsRegistry, MetricsServer,
                           check_request_order)
from repro.serving import events as ev
from repro.serving.observability.httpd import parse_hostport
from repro.serving.transport import protocol
from repro.serving.transport.server import request_json

CFG = reduce_ppm_config()
PARAMS = init_ppm(jax.random.PRNGKey(0), CFG)
RNG = np.random.default_rng(13)


def _seq(length: int) -> np.ndarray:
    return RNG.integers(0, 20, length).astype(np.int32)


def _client(**kw) -> FoldClient:
    kw.setdefault("buckets", (32,))
    kw.setdefault("max_tokens_per_batch", 64)
    kw.setdefault("max_batch", 2)
    return FoldClient(PARAMS, CFG, "lightnobel_aaq", **kw)


def _router(n: int = 2, *, autostart: bool = False, **kw) -> FleetRouter:
    return FleetRouter(lambda i: _client(**kw), n, autostart=autostart)


def _get_raw(url: str) -> tuple[int, bytes]:
    with urllib.request.urlopen(url, timeout=30.0) as resp:
        return resp.status, resp.read()


# --------------------------------------------------------------------------
# protocol: pure wire-schema round-trips (no sockets, no engine)
# --------------------------------------------------------------------------
def test_array_roundtrip_is_bitwise():
    for arr in (np.linspace(-3, 7, 12, dtype=np.float32).reshape(4, 3),
                np.arange(6, dtype=np.int32),
                RNG.standard_normal((2, 5, 5)).astype(np.float64)):
        back = protocol.decode_array(protocol.encode_array(arr))
        assert back.dtype == arr.dtype and back.shape == arr.shape
        assert back.tobytes() == arr.tobytes()


def test_decode_array_rejects_malformed_payloads():
    with pytest.raises(protocol.ProtocolError):
        protocol.decode_array({"shape": [3], "dtype": "float32"})
    with pytest.raises(protocol.ProtocolError):
        protocol.decode_array({"shape": [4], "dtype": "nope", "b64": "AA=="})


def test_parse_sequence_string_and_ids():
    assert protocol.parse_sequence("ARNDX").tolist() == [0, 1, 2, 3, 20]
    assert protocol.parse_sequence(" arnd ").tolist() == [0, 1, 2, 3]
    assert protocol.parse_sequence([5, 0, 19]).dtype == np.int32
    for bad in ("", "AB1", [], [0, 21], [[0, 1]], 42, [0.5]):
        with pytest.raises(protocol.ProtocolError):
            protocol.parse_sequence(bad)


def test_parse_submit_validates_fields():
    seq, pri, dl = protocol.parse_submit(
        json.dumps({"sequence": "ARND", "priority": 2,
                    "deadline_s": 1.5}).encode())
    assert seq.tolist() == [0, 1, 2, 3] and pri == 2 and dl == 1.5
    _, pri, dl = protocol.parse_submit(json.dumps({"sequence": [4]}).encode())
    assert pri == 0 and dl is None
    for bad in (b"not json", b"[1,2]",
                json.dumps({"priority": 1}).encode(),
                json.dumps({"sequence": "A", "bogus": 1}).encode(),
                json.dumps({"sequence": "A", "priority": "hi"}).encode(),
                json.dumps({"sequence": "A", "priority": True}).encode(),
                json.dumps({"sequence": "A", "deadline_s": -2}).encode()):
        with pytest.raises(protocol.ProtocolError):
            protocol.parse_submit(bad)


def test_event_and_sse_roundtrip():
    events = [
        ev.FoldEvent(seq=7, kind=ev.SUBMITTED, request_id=3, t=1.0,
                     data={"length": 20}),
        ev.FoldEvent(seq=9, kind=ev.BATCH_START, request_id=3, t=2.0,
                     data={"request_ids": (3, 4)}),
        ev.FoldEvent(seq=12, kind=ev.COMPLETED, request_id=3, t=3.0,
                     data={}),
    ]
    for e in events:
        back = protocol.decode_event(protocol.encode_event(e))
        assert (back.seq, back.kind, back.request_id, back.t) == \
            (e.seq, e.kind, e.request_id, e.t)
    body = b"".join(protocol.sse_frame(e) for e in events)
    assert body.startswith(b"id: 7\nevent: submitted\ndata: ")
    parsed = protocol.parse_sse(body)
    assert [e.kind for e in parsed] == [e.kind for e in events]
    assert parsed[1].data["request_ids"] == [3, 4]   # tuple -> list on wire


def test_parse_hostport():
    assert parse_hostport("127.0.0.1:8080") == ("127.0.0.1", 8080)
    assert parse_hostport("9090") == ("127.0.0.1", 9090)
    assert parse_hostport("0.0.0.0:0") == ("0.0.0.0", 0)
    for bad in ("", "host:", "host:abc", "host:70000", "host:-1"):
        with pytest.raises(ValueError):
            parse_hostport(bad)


# --------------------------------------------------------------------------
# httpd base: ephemeral-port binding (the PR-6 MetricsServer fix)
# --------------------------------------------------------------------------
class _RegistryOwner:
    """The minimal surface MetricsServer scrapes (a FoldClient stand-in)."""
    driving = False
    pending = 0

    def __init__(self, reg: MetricsRegistry):
        self.reg = reg

    def metrics_text(self) -> str:
        return self.reg.prometheus_text()

    def metrics_json(self) -> dict:
        return self.reg.as_dict()


def test_metrics_server_binds_ephemeral_port_and_reports_it():
    reg = MetricsRegistry()
    reg.counter("demo_total", "demo").inc()
    with MetricsServer(_RegistryOwner(reg), port=0) as srv:
        assert srv.port != 0
        assert f":{srv.port}" in srv.url
        status, body = _get_raw(f"{srv.url}/metrics")
        assert status == 200 and b"demo_total 1" in body
        status, body = _get_raw(f"{srv.url}/healthz")
        assert status == 200 and json.loads(body)["ok"] is True


# --------------------------------------------------------------------------
# fleet router: telemetry-driven routing + failure isolation (no HTTP)
# --------------------------------------------------------------------------
def test_router_prefers_idle_replica_by_injected_telemetry():
    router = _router(2, autostart=False)
    try:
        # load is read from each replica's own registry: steering the
        # gauge steers the routing (tests and scrapers see one truth)
        r0, r1 = router.replicas
        r0.registry.gauge("fold_queue_depth").set(5)
        assert router.pick_replica() is r1
        r0.registry.gauge("fold_queue_depth").set(0)
        r1.registry.gauge("fold_queue_depth").set(3)
        assert router.pick_replica() is r0
        # ties break deterministically on the lowest index
        r1.registry.gauge("fold_queue_depth").set(0)
        assert router.pick_replica() is r0
        # inflight is the secondary key
        r0.registry.gauge("fold_inflight_batches").set(2)
        assert router.pick_replica() is r1
    finally:
        router.stop()


def test_replica_failure_requeues_queued_requests():
    router = _router(2, autostart=False)
    try:
        recs = [router.submit(_seq(16 + i), priority=i % 2)
                for i in range(3)]
        assert recs[0].replica_index == 0      # first route: tie -> index 0
        assert all(r.handle.status == "QUEUED" for r in recs)

        router.replicas[0].mark_failed()
        requeued = router.check_health()
        victims = [r for r in recs if r.requeues]
        assert requeued and {r.request_id for r in victims} == set(requeued)
        assert all(r.replica_index == 1 for r in recs)   # all on the healthy one
        assert router.registry.get("fleet_requeued_total").total() == \
            len(victims)

        router.start()                         # starts only healthy replicas
        assert not router.replicas[0].started
        results = [r.handle.result(timeout=600.0) for r in recs]
        assert all(res.ok for res in results)
        # one legal per-request event stream, exactly one SUBMITTED each
        for rec in recs:
            check_request_order(rec.events)
            kinds = [e.kind for e in rec.events]
            assert kinds.count(ev.SUBMITTED) == 1
            assert kinds[-1] == ev.COMPLETED
    finally:
        router.stop()


def test_router_with_all_replicas_dead_raises():
    router = _router(1, autostart=False)
    router.replicas[0].mark_failed()
    router.check_health()
    with pytest.raises(RuntimeError):
        router.submit(_seq(8))


# --------------------------------------------------------------------------
# HTTP server over a real socket
# --------------------------------------------------------------------------
def test_http_submit_status_result_bitwise_and_lazy_distogram():
    client = _client(fidelity=False)
    seq = _seq(24)
    ref = client.submit(seq).result()          # in-process reference

    router = FleetRouter.wrap(client, autostart=True)
    try:
        with FoldHTTPServer(router) as srv:
            assert srv.port != 0               # ephemeral bind resolved
            resp = request_json(f"{srv.url}/v1/fold", method="POST",
                                body={"sequence": seq.tolist(),
                                      "priority": 1})
            rid = resp["id"]
            assert resp["v"] == protocol.PROTOCOL_VERSION
            assert resp["events_url"] == f"/v1/fold/{rid}/events"
            rec = router.get(rid)
            rec.handle.result(timeout=600.0)

            status = request_json(f"{srv.url}/v1/fold/{rid}")
            assert status["state"] == "DONE" and status["done"]
            coords = protocol.decode_array(status["result"]["coords"])
            assert coords.tobytes() == ref.coords.tobytes()

            # plain polls never ship (or materialize) the distogram
            assert status["result"]["distogram"] is None
            assert rec.handle._result.distogram.materialized is False
            with_dist = request_json(f"{srv.url}/v1/fold/{rid}?distogram=1")
            dist = protocol.decode_array(with_dist["result"]["distogram"])
            assert rec.handle._result.distogram.materialized is True
            np.testing.assert_array_equal(
                dist, np.asarray(rec.handle._result.distogram))

            # decode_result restores a usable FoldResult, arrays bitwise
            restored = protocol.decode_result(with_dist["result"])
            assert restored.ok
            assert restored.coords.tobytes() == ref.coords.tobytes()

            # unknown id -> 404; malformed submit -> 400
            with pytest.raises(urllib.error.HTTPError) as ei:
                request_json(f"{srv.url}/v1/fold/999999")
            assert ei.value.code == 404
            with pytest.raises(urllib.error.HTTPError) as ei:
                request_json(f"{srv.url}/v1/fold", method="POST",
                             body={"sequence": "AB1"})
            assert ei.value.code == 400
    finally:
        router.stop()


def test_http_cancel_and_sse_stream_order():
    router = _router(1, autostart=False)       # nothing runs until start()
    try:
        with FoldHTTPServer(router) as srv:
            rid = request_json(f"{srv.url}/v1/fold", method="POST",
                               body={"sequence": _seq(16).tolist()})["id"]
            resp = request_json(f"{srv.url}/v1/fold/{rid}", method="DELETE")
            assert resp["cancelled"] is True
            assert resp["state"] == "CANCELLED"
            status = request_json(f"{srv.url}/v1/fold/{rid}")
            assert status["state"] == "CANCELLED" and status["done"]
            assert status["result"]["status"] == "cancelled"
            # cancel is idempotent at the HTTP layer: already-terminal
            resp = request_json(f"{srv.url}/v1/fold/{rid}", method="DELETE")
            assert resp["cancelled"] is False

            # SSE: the stream replays history and closes at the terminal
            # event, so a plain read yields the full ordered story
            _, body = _get_raw(f"{srv.url}/v1/fold/{rid}/events")
            events = protocol.parse_sse(body)
            check_request_order(events)
            assert [e.kind for e in events] == [ev.SUBMITTED, ev.CANCELLED]
            assert all(e.request_id == rid for e in events)
    finally:
        router.stop()


def test_http_fleet_endpoints_and_metrics():
    router = _router(2, autostart=False)
    try:
        with FoldHTTPServer(router) as srv:
            hz = request_json(f"{srv.url}/healthz")
            assert hz["ok"] and len(hz["replicas"]) == 2
            fleet = request_json(f"{srv.url}/v1/fleet")
            assert fleet["replicas"] == 2 and fleet["healthy"] == 2
            status, body = _get_raw(f"{srv.url}/metrics")
            assert status == 200
            text = body.decode()
            for series in ("fleet_replica_healthy", "fleet_live_records",
                           "fleet_replica_queue_depth"):
                assert series in text
            mj = request_json(f"{srv.url}/metrics.json")
            assert "fleet_replica_healthy" in mj
            _, body = _get_raw(f"{srv.url}/metrics/replica/1")
            assert b"fold_queue_depth" in body
            with pytest.raises(urllib.error.HTTPError) as ei:
                request_json(f"{srv.url}/metrics/replica/7")
            assert ei.value.code == 404
    finally:
        router.stop()


# --------------------------------------------------------------------------
# acceptance: 2-replica fleet over HTTP matches the in-process client
# --------------------------------------------------------------------------
def test_fleet_http_end_to_end_matches_inprocess():
    sampler = ProteinSampler(seed=11, min_len=40, max_len=64)
    trace = [sampler.sample(i) for i in range(8)]
    priorities = [1 - (i % 2) for i in range(8)]   # mixed tiers
    shape = dict(buckets=(64,), max_tokens_per_batch=128, fidelity=False)

    reference = _client(**shape)
    ref_results = [reference.submit(s, priority=p)
                   for s, p in zip(trace, priorities)]
    reference.drive()
    ref_results = [h.result() for h in ref_results]

    router = _router(2, autostart=True, **shape)
    try:
        with FoldHTTPServer(router) as srv:
            ids = [request_json(f"{srv.url}/v1/fold", method="POST",
                                body={"sequence": s.tolist(), "priority": p}
                                )["id"]
                   for s, p in zip(trace, priorities)]
            router.drain_wait(timeout=600.0)
            statuses = [request_json(f"{srv.url}/v1/fold/{rid}")
                        for rid in ids]
        for st, ref in zip(statuses, ref_results):
            assert st["state"] == "DONE"
            got = protocol.decode_array(st["result"]["coords"])
            # the fleet (whichever replica served it, whatever batch it
            # rode in) matches the in-process pump under the contract
            assert_fold_parity("lightnobel_aaq", got, ref.coords)
            assert st["result"]["priority"] == ref.priority
        # the router's choices are visible in fleet telemetry: every
        # request accounted for across the routed-by-replica counters
        routed = router.registry.get("fleet_routed_total")
        assert routed.total() == len(trace)
        # per-request event history arrived intact and legal
        for rid in ids:
            rec = router.get(rid)
            check_request_order(rec.events)
            assert [e.kind for e in rec.events][-1] == ev.COMPLETED
    finally:
        router.stop()
