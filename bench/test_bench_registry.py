"""Every cell of BENCHMARK.json resolves by name to its files; the traffic
generator keeps its promises; the harness refuses to run without a chip."""
from __future__ import annotations

import collections
import importlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import run
from bench.traffic import backlog

ROOT = Path(__file__).resolve().parents[1]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


def traffic_of(cell):
    w = run.cell(SPEC, cell)
    return json.loads((ROOT / "bench" / "traffic"
                       / f"{w['traffic']}.json").read_text())


def caps_of(traffic):
    s = traffic["serve"]
    return {b: min(s["max_batch"], max(1, s["max_tokens_per_batch"] // b))
            for b in traffic["buckets"]}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    w = run.cell(SPEC, cell)
    cfg_path = run.config_file(SPEC, w["config"])
    assert cfg_path.is_file() and cfg_path.is_relative_to(ROOT / "bench")
    cfg = json.loads(cfg_path.read_text())
    entry = next(c for c in SPEC["configs"] if c["name"] == w["config"])
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    traffic = traffic_of(cell)
    gen = run.traffic_generator(traffic)
    assert hasattr(gen, "Backlog")
    for m in run.per_layer(SPEC, cell):
        assert callable(run.metric_reader(m["name"]))
        assert m["moves"] in {e["name"] for e in run.end_to_end(SPEC, cell)}
    names = {m["name"] for m in run.end_to_end(SPEC, cell)}
    assert {"setup_s", "folds_per_s"} <= names
    assert run.per_layer(SPEC, cell)


def test_every_name_is_used_and_unique():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[key]]
        assert len(names) == len(set(names)), key
    for m in SPEC["per_layer"]:
        assert set(m.get("workloads", [])) <= set(CELLS)
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))


@pytest.mark.parametrize("cell", CELLS)
def test_config_matches_the_program(cell):
    """The configuration file states what the server runs: its sizes build
    the program's config, and its AAQ table is the site table of the scheme
    the harness serves with."""
    w = run.cell(SPEC, cell)
    cfg = json.loads(run.config_file(SPEC, w["config"]).read_text())
    run.program_config(cfg)
    scheme = run.program_scheme(cfg)
    assert scheme.name == cfg["scheme"]
    if cfg["aaq"] is None:
        assert cfg["scheme"] == "baseline_fp16"
        return
    for site, group in cfg["aaq"]["sites"].items():
        for op in ("tri_mul_out", "tri_attn_end", "pair_trans"):
            pol = scheme.cfg.policy_for(f"{op}.{site}")
            assert [pol.bits, pol.k_outliers] == cfg["aaq"]["groups"][group]


@pytest.mark.parametrize("cell", CELLS)
def test_backlog_lengths_and_order(cell):
    traffic = traffic_of(cell)
    caps = caps_of(traffic)
    lengths = backlog.cycle_lengths(traffic["lengths"])
    assert len(lengths) == traffic["lengths"]["per_cycle"]
    per_bucket = collections.Counter(
        backlog.bucket_of(traffic["buckets"], n) for n in lengths)
    orders = []
    for seed in (0, 1, 2 ** 31 + 12345):
        gen = backlog.Backlog(traffic, seed, caps)
        batches = [gen.next_batch() for _ in range(60)]
        buckets = [b for b, _ in batches]
        orders.append(buckets)
        for b, seqs in batches:
            assert len(seqs) == caps[b]
            assert all(len(s) <= b and s.dtype == np.int32 for s in seqs)
        # every whole cycle of a bucket serves that bucket's lengths
        for b, count in per_bucket.items():
            got = [len(s) for bb, seqs in batches if bb == b for s in seqs]
            whole = len(got) // count * count
            want = sorted(n for n in lengths
                          if backlog.bucket_of(traffic["buckets"], n) == b)
            for i in range(0, whole, count):
                assert sorted(got[i:i + count]) == want
        seq_lengths = [len(s) for _, seqs in batches for s in seqs]
        if seed == 0:
            first = seq_lengths
        else:
            assert seq_lengths != first or len(set(first)) == 1
    # the order of the buckets is the same for every seed
    assert orders[0] == orders[1] == orders[2]


def test_bucket_order_tracks_weights():
    gen = backlog.bucket_order({128: 1.0, 256: 3.0, 512: 4.0})
    seq = [next(gen) for _ in range(80)]
    assert collections.Counter(seq) == {128: 10, 256: 30, 512: 40}


def test_seeds_make_the_same_weights():
    from bench.weights import make_params
    cfg = json.loads((ROOT / "bench/testdata/tiny_aaq.json").read_text())
    a = make_params(cfg, 2 ** 33 + 7)
    b = make_params(cfg, 2 ** 33 + 7)
    c = make_params(cfg, 7)
    leaf = lambda t: np.asarray(t["trunk"]["opm"]["out"]["w"], np.float32)
    np.testing.assert_array_equal(leaf(a), leaf(b))
    assert not np.array_equal(leaf(a), leaf(c))


def test_weights_have_the_servers_layout():
    from bench.weights import make_params
    from repro.models.ppm import init_ppm
    cfg = json.loads((ROOT / "bench/testdata/tiny_aaq.json").read_text())
    mine = jax.eval_shape(lambda: make_params(cfg, 1))
    theirs = jax.eval_shape(lambda k: init_ppm(k, run.program_config(cfg)),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_unknown_device_kind_is_refused():
    with pytest.raises(run.Refused):
        run.peak_entry("TPU v99 imaginary")
    assert run.peak_entry("TPU v5 lite")["bf16_flops_s"] == 197e12


def _bench(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(Path(cwd) / "bench" / "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_with_no_result():
    p = _bench(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_metric_modules_define_read():
    for m in SPEC["per_layer"]:
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        assert callable(mod.read)
