"""The comparison that decides ``correct``, at a test size on the CPU.

* The plain reference computes what the server computes: in float32 under
  the FP scheme the two agree to rounding, and its AAQ is the server's.
* A whole run (everything but the look for a chip) is correct when sound,
  and not correct when its timed path is broken underneath: an answer
  altered where it is produced (every fold mirrored), or half of each batch
  left out (its rows answered with the other half's folds).
* The control, the reference in float8 put in the server's place, breaks a
  limit of each configuration.

The test configurations (``bench/testdata/tiny_*.json``) are the cells'
models at widths a test can hold, with limits set from CPU readings the
same way the cells' limits are set from chip readings.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, correct, run
from bench.reference import ppm as ref
from bench.weights import make_params

DATA = Path(__file__).resolve().parent / "testdata"
SPEC = json.loads((DATA.parents[1] / "BENCHMARK.json").read_text())
TRAFFIC = json.loads((DATA / "tiny_backlog.json").read_text())
SEED = 2 ** 32 + 17


def tiny(name: str) -> dict:
    return json.loads((DATA / name).read_text())


@pytest.fixture
def no_compile_cache(monkeypatch):
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)


@pytest.mark.parametrize("bucket,chunk", [(37, None), (64, None), (64, 16)])
def test_reference_is_the_server_in_float32(bucket, chunk):
    """Unpadded, padded to a bucket, and padded with the pair stack
    row-chunked: the server's fold of 37 residues is the reference's, which
    pads it the same way."""
    from repro.core import make_scheme
    from repro.models.ppm import ppm_forward
    from repro.serving import pad_to_bucket
    cfg = dict(tiny("tiny_fp16.json"), dtype="float32")
    params = make_params(cfg, SEED)
    seq = np.random.default_rng(0).integers(0, 20, 37).astype(np.int32)
    aat, mask = pad_to_bucket([seq], bucket)
    out = jax.jit(lambda p, a, m: ppm_forward(
        p, a, run.model_of(cfg).program_config(cfg),
        make_scheme("baseline_fp16"), mask=m,
        chunk_size=chunk))(params, jnp.asarray(aat), jnp.asarray(mask))
    out = {k: v[:, :37, :37] if k == "distogram" else v[:, :37]
           for k, v in out.items() if k in ("coords", "distogram")}
    coords, disto = ref.Reference(cfg)(params, seq, bucket)
    got = correct.readings(np.asarray(out["coords"][0]),
                           np.asarray(out["distogram"][0]), coords, disto)
    assert got["coords_rel_rms"] < 1e-4
    assert got["disto_rel_rms"] < 1e-4


@pytest.mark.parametrize("bits,k", [(8, 4), (4, 4), (4, 0)])
def test_reference_aaq_is_the_servers(bits, k):
    from repro.core.quantize import fake_quant
    x = jax.random.normal(jax.random.PRNGKey(bits + k), (64, 128)) * 3.0
    x = x.astype(jnp.bfloat16).astype(jnp.float32)   # outliers exact in bf16
    np.testing.assert_allclose(np.asarray(ref.fake_quant(x, bits, k)),
                               np.asarray(fake_quant(x, bits, k)),
                               rtol=1e-6, atol=1e-6)


def _run(name: str):
    """A whole run of the test configuration ``name`` under the test
    traffic, through a cell of its own."""
    spec = dict(SPEC, configs=[{"name": "tiny",
                                "file": f"bench/testdata/{name}"}],
                workloads=[{"name": "tiny.short", "config": "tiny",
                            "traffic": "../testdata/tiny_backlog",
                            "chips": 1}])
    return run.run_cell(spec, "tiny.short", SEED, 2.0, False)


def test_sound_run_is_correct(no_compile_cache):
    out = _run("tiny_aaq.json")
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(tiny("tiny_aaq.json")["limits"])


def _mirrored(orig):
    def forward(self, scheme, chunk, params, aatype, mask):
        out = orig(self, scheme, chunk, params, aatype, mask)
        return dict(out, coords=out["coords"] * jnp.array([1.0, 1.0, -1.0]))
    return forward


def _half_batch(orig):
    def forward(self, scheme, chunk, params, aatype, mask):
        b = aatype.shape[0]
        h = max(1, b // 2)
        out = orig(self, scheme, chunk, params, aatype[:h], mask[:h])
        rows = jnp.arange(b) % h
        return {k: v[rows] for k, v in out.items()}
    return forward


@pytest.mark.parametrize("fault", [_mirrored, _half_batch])
def test_broken_run_is_not_correct(no_compile_cache, monkeypatch, fault):
    from repro.serving.workload import FoldWorkload
    monkeypatch.setattr(FoldWorkload, "forward",
                        fault(FoldWorkload.forward))
    out = _run("tiny_aaq.json")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("name", ["tiny_aaq.json", "tiny_fp16.json"])
def test_control_is_not_correct(name):
    cfg = tiny(name)
    got = control.control_readings(cfg, TRAFFIC, SEED)
    assert any(got[k] > v for k, v in cfg["limits"].items()), got
