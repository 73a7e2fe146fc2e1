"""Mesh-sharded serving tier tests.

In-process tests cover the placement policy, mesh-spec parsing, per-device
admission accounting, and placement threading through the scheduler and the
reports — none of which need devices.  The end-to-end parity/admission/
zero-recompile gate runs in a subprocess with 8 forced host devices (the
XLA device-count flag must precede jax import), the test_distributed
pattern.

Parity contract: under the FP baseline scheme, sharded coords must be
allclose to the single-device engine at tight tolerance (the only noise is
GSPMD reduction reordering, observed ~2e-6).  Under the AAQ scheme, tiny
reduction-order differences can flip quantization-bin assignments and
amplify through the trunk, so the gate is the paper's own fidelity metric:
TM-score vs the single-device serve >= 0.995 (observed >= 0.9997).
"""
import io
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.configs import reduce_ppm_config
from repro.core import make_scheme
from repro.serving import (ADMIT, REJECT, AdmissionController, FoldRequest,
                           FoldResult, PlacementPolicy, TokenBudgetScheduler,
                           csv_row, parse_mesh_spec)
from repro.serving.placement import SINGLE_PLACEMENT

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
CFG = reduce_ppm_config()
SCHEME = make_scheme("lightnobel_aaq")
RNG = np.random.default_rng(23)


def _seq(length: int) -> np.ndarray:
    return RNG.integers(0, 20, length).astype(np.int32)


class _FakeMesh:
    """Enough mesh surface for PlacementPolicy without real devices."""
    axis_names = ("data", "model")

    def __init__(self, data: int, model: int):
        self.shape = {"data": data, "model": model}
        self.devices = np.zeros((data, model))


# --------------------------------------------------------------------------
# mesh spec / policy
# --------------------------------------------------------------------------
def test_parse_mesh_spec():
    assert parse_mesh_spec("2x4") == (2, 4)
    assert parse_mesh_spec("1X8") == (1, 8)
    for bad in ("2", "2x", "axb", "0x4", "2x4x2"):
        with pytest.raises(ValueError):
            parse_mesh_spec(bad)


def test_make_serving_mesh_none_and_too_big():
    from repro.serving import make_serving_mesh
    assert make_serving_mesh(None) is None
    assert make_serving_mesh("") is None
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        make_serving_mesh("64x64")           # way beyond any host


def test_placement_policy_thresholds_and_labels():
    none = PlacementPolicy()
    assert none.placement_for(512) is SINGLE_PLACEMENT
    assert none.shards_for(512) == 1

    pol = PlacementPolicy(mesh=_FakeMesh(2, 4), shard_threshold=64)
    assert pol.placement_for(32) is SINGLE_PLACEMENT   # below threshold
    p = pol.placement_for(64)
    assert p.sharded and p.model_shards == 4 and p.label == "mesh:2x4"
    assert pol.placement_for(128).sharded
    assert pol.shards_for(64) == 4 and pol.shards_for(32) == 1
    assert "," not in p.label                          # must survive CSV rows

    # a bucket the model axis does not divide honestly stays single
    odd = PlacementPolicy(mesh=_FakeMesh(1, 3), shard_threshold=16)
    assert odd.placement_for(32) is SINGLE_PLACEMENT
    assert odd.placement_for(48).sharded

    with pytest.raises(ValueError, match="model"):
        class NoModel:
            axis_names = ("data",)
        PlacementPolicy(mesh=NoModel(), shard_threshold=16)

    # a mesh nothing routes to (or a threshold with nowhere to shard) is a
    # config error, not a silent everything-single-device no-op
    with pytest.raises(ValueError, match="together"):
        PlacementPolicy(mesh=_FakeMesh(2, 4))
    with pytest.raises(ValueError, match="together"):
        PlacementPolicy(shard_threshold=64)


# --------------------------------------------------------------------------
# per-device admission accounting
# --------------------------------------------------------------------------
def test_admission_per_device_share_and_flip():
    flat = AdmissionController(CFG, SCHEME)
    total = flat.estimate_bytes(64, 1)
    # explicit shards: ceil(total / k)
    assert flat.estimate_bytes(64, 1, shards=4) == -(-total // 4)
    # shards_for wiring: the controller prices per device by itself
    sharded = AdmissionController(CFG, SCHEME, mem_budget_bytes=total - 1,
                                  shards_for=lambda ns: 4 if ns >= 64 else 1)
    solo = AdmissionController(CFG, SCHEME, mem_budget_bytes=total - 1)
    # the flip: the same bucket busts the per-device budget alone on one
    # device but is admitted when sharding divides its share
    assert solo.admit(64, 1).verdict == REJECT
    d = sharded.admit(64, 1)
    assert d.verdict == ADMIT and d.shards == 4
    assert d.est_bytes == -(-total // 4)
    # below the threshold nothing changes
    assert sharded.admit(32, 1).verdict == solo.admit(32, 1).verdict
    # reject reasons name the per-device share
    r = sharded.admit(128, 1)        # big bucket still over even sharded?
    if r.verdict == REJECT:
        assert "/device" in r.reason
    assert sharded.max_batch_for(64, 8) >= solo.max_batch_for(64, 8)
    ex = sharded.explain(64, 1)
    assert ex["shards"] == 4
    assert ex["per_device_mb"] == pytest.approx(ex["total_mb"] / 4, rel=1e-3)


# --------------------------------------------------------------------------
# scheduler / report threading
# --------------------------------------------------------------------------
def test_scheduled_batch_carries_placement_label():
    pol = PlacementPolicy(mesh=_FakeMesh(2, 4), shard_threshold=64)
    sched = TokenBudgetScheduler((32, 64), max_tokens_per_batch=128,
                                 placement=pol)
    sched.submit(FoldRequest(0, _seq(20)), now=0.0)
    sched.submit(FoldRequest(1, _seq(50)), now=1.0)
    batches = {}
    while sched.pending:
        b = sched.next_batch()
        batches[b.bucket] = b.placement
    assert batches == {32: "single", 64: "mesh:2x4"}
    # no policy = the old single-device label everywhere
    plain = TokenBudgetScheduler((64,))
    plain.submit(FoldRequest(0, _seq(50)), now=0.0)
    assert plain.next_batch().placement == "single"


def test_placement_in_csv_and_json_reports():
    from repro.serving import EngineMetrics
    r = FoldResult(request_id=0, length=50, bucket=64, batch_size=1,
                   coords=np.zeros((50, 3), np.float32),
                   kernel_backend="auto:ref", placement="mesh:2x4")
    assert csv_row(r).endswith(",auto:ref,mesh:2x4,0")
    m = EngineMetrics()
    m.record(r)
    buf = io.StringIO()
    m.write_json(buf)
    assert '"placement": "mesh:2x4"' in buf.getvalue()
    buf = io.StringIO()
    m.write_csv(buf)
    header, row = buf.getvalue().strip().splitlines()
    assert header.endswith(",kernel_backend,placement,chunk_size")
    assert row.split(",")[-2] == "mesh:2x4"


# --------------------------------------------------------------------------
# the end-to-end gate: 8 forced host devices, out of process
# --------------------------------------------------------------------------
def _run(body: str) -> str:
    code = "import os\nos.environ['XLA_FLAGS']=" \
           "'--xla_force_host_platform_device_count=8'\n" + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900,
                       env={**os.environ, "PYTHONPATH": SRC})
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_sharded_serving_parity_admission_and_steady_state():
    """The acceptance gate, on a 2x4 CPU mesh with shard threshold 64:

    1. FP-scheme sharded coords allclose (tight) to the single-device
       engine; AAQ-scheme fidelity TM >= 0.995 vs single-device.
    2. A per-device budget that rejects bucket 64 unsharded at submit
       ADMITS and serves it on the mesh (the paper's scalability story as
       an admission verdict).
    3. Zero recompiles across repeated sharded batches of the same bucket.
    4. The placement label rides FoldResult, the CSV report, and the
       SCHEDULED event.
    """
    out = _run("""
    import io, numpy as np, jax
    from repro.configs import reduce_ppm_config
    from repro.models.ppm import init_ppm, tm_score
    from repro.serving import (AdmissionController, FoldClient,
                               make_serving_mesh)
    from repro.serving import events as ev
    from repro.core import make_scheme

    cfg = reduce_ppm_config()
    params = init_ppm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    seqs = [rng.integers(0, 20, n).astype(np.int32) for n in (50, 60)]
    mesh = make_serving_mesh("2x4")
    assert len(jax.devices()) == 8

    # per-device budget: bucket 64 busts it alone unsharded, fits /4
    est = AdmissionController(cfg, make_scheme("lightnobel_aaq")).estimate_bytes(64, 1)
    budget_mb = (est - 1) / 1e6

    # -- 2. admission flip: unsharded client rejects at submit ------------
    solo_budget = FoldClient(params, cfg, "lightnobel_aaq", buckets=(64,),
                             max_tokens_per_batch=128, max_batch=2,
                             mem_budget_mb=budget_mb)
    h = solo_budget.submit(seqs[0])
    assert h.status == "REJECTED" and "budget" in h.result().reason, h
    print("FLIP_REJECT_OK")

    # -- sharded client under the SAME per-device budget serves ----------
    sharded = FoldClient(params, cfg, "lightnobel_aaq", buckets=(64,),
                         max_tokens_per_batch=128, max_batch=2,
                         mesh=mesh, shard_threshold=64,
                         mem_budget_mb=budget_mb)
    stream = sharded.stream()
    rs = {h.request_id: h.result() for h in [sharded.submit(s) for s in seqs]}
    assert all(r.ok for r in rs.values())
    assert all(r.placement == "mesh:2x4" for r in rs.values()), rs
    sch = [e for e in stream.events() if e.kind == ev.SCHEDULED]
    assert sch and all(e.data["placement"] == "mesh:2x4" for e in sch), sch
    print("FLIP_ADMIT_OK")

    # -- 3. steady state: same bucket again, zero new executables --------
    n0 = sharded.core.compile_count
    for h in [sharded.submit(s) for s in seqs]:
        assert h.result().ok
    assert sharded.core.compile_count == n0, "sharded steady state recompiled"
    print("STEADY_OK", n0)

    # -- 4. placement label in the CSV report ----------------------------
    buf = io.StringIO()
    sharded.metrics.write_csv(buf)
    rows = [l for l in buf.getvalue().splitlines()[1:] if l]
    assert all(r.endswith(",mesh:2x4,0") for r in rows), rows
    print("REPORT_OK")

    # -- 1. parity: AAQ fidelity gate vs single-device -------------------
    single = FoldClient(params, cfg, "lightnobel_aaq", buckets=(64,),
                        max_tokens_per_batch=128, max_batch=2)
    r1 = {h.request_id: h.result() for h in [single.submit(s) for s in seqs]}
    for rid, r in r1.items():
        tm = float(tm_score(rs[rid].coords, r.coords))
        assert tm >= 0.995, (rid, tm)
        assert rs[rid].coords.shape == r.coords.shape
    print("AAQ_TM_OK")

    # -- 1b. FP scheme: strict allclose (reduction reordering only) ------
    sh_fp = FoldClient(params, cfg, None, buckets=(64,),
                       max_tokens_per_batch=128, max_batch=2,
                       mesh=mesh, shard_threshold=64)
    si_fp = FoldClient(params, cfg, None, buckets=(64,),
                       max_tokens_per_batch=128, max_batch=2)
    fs = {h.request_id: h.result() for h in [sh_fp.submit(s) for s in seqs]}
    f1 = {h.request_id: h.result() for h in [si_fp.submit(s) for s in seqs]}
    for rid in fs:
        np.testing.assert_allclose(fs[rid].coords, f1[rid].coords,
                                   rtol=1e-4, atol=1e-4)
    print("FP_PARITY_OK")
    """)
    for marker in ("FLIP_REJECT_OK", "FLIP_ADMIT_OK", "STEADY_OK",
                   "REPORT_OK", "AAQ_TM_OK", "FP_PARITY_OK"):
        assert marker in out


def test_sharded_pallas_kernels_run_per_device():
    """GSPMD cannot partition a Mosaic kernel, so under the sharded rules
    dispatch runs each Pallas call per device (``shard_map``).  On a 1x4
    CPU mesh with the kernels interpreted, the sharded serve must keep the
    Pallas path (trace counters, backend label) and hold the placement
    parity contract against the single-device Pallas serve."""
    out = _run("""
    import numpy as np, jax
    from repro.configs import reduce_ppm_config
    from repro.kernels import dispatch
    from repro.models.ppm import init_ppm, tm_score
    from repro.serving import FoldClient, make_serving_mesh

    cfg = reduce_ppm_config()
    params = init_ppm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    seqs = [rng.integers(0, 20, n).astype(np.int32) for n in (50, 60)]
    mesh = make_serving_mesh("1x4")
    kw = dict(buckets=(64,), max_tokens_per_batch=128, max_batch=2,
              kernels="pallas")
    for scheme in ("lightnobel_aaq", None):
        sh = FoldClient(params, cfg, scheme, mesh=mesh, shard_threshold=64,
                        **kw)
        dispatch.reset_counters()
        a = [h.result() for h in [sh.submit(s) for s in seqs]]
        assert dispatch.counters["attention.pallas"] > 0
        assert dispatch.counters["attention.ref"] == 0
        if scheme:
            assert dispatch.counters["qmatmul.pallas"] > 0
            assert dispatch.counters["qmatmul.ref"] == 0
        si = FoldClient(params, cfg, scheme, **kw)
        b = [h.result() for h in [si.submit(s) for s in seqs]]
        for x, y in zip(a, b):
            assert x.ok and y.ok, (x.reason, y.reason)
            assert x.placement == "mesh:1x4" and y.placement == "single"
            assert x.kernel_backend == "pallas-interpret", x.kernel_backend
            if scheme:
                assert float(tm_score(x.coords, y.coords)) >= 0.995
            else:
                np.testing.assert_allclose(x.coords, y.coords, rtol=1e-4,
                                           atol=1e-4)
    print("SHARDED_PALLAS_OK")
    """)
    assert "SHARDED_PALLAS_OK" in out
