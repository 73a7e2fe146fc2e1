"""Every cell of BENCHMARK.json resolves by name to its files; the traffic
generator keeps its promises; every model module makes seeded weights in
its program's layout; a second model goes in by new files alone; the
harness refuses to run without a chip."""
from __future__ import annotations

import collections
import hashlib
import importlib
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import run
from bench.traffic import backlog

ROOT = Path(__file__).resolve().parents[1]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
MODELS = sorted(p.stem for p in (ROOT / "bench" / "models").glob("*.py")
                if p.stem != "__init__")


def traffic_of(cell):
    w = run.cell(SPEC, cell)
    return json.loads((ROOT / "bench" / "traffic"
                       / f"{w['traffic']}.json").read_text())


BACKLOG_CELLS = [c for c in CELLS if traffic_of(c)["kind"] == "backlog"]


def model_and_tiny(name):
    model = importlib.import_module(f"bench.models.{name}")
    return model, json.loads((ROOT / model.TINY).read_text())


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
    assert hasattr(gen, "Backlog") and callable(gen.length)
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
    run.model_of(cfg).program_config(cfg)
    scheme = run.program_scheme(cfg)
    assert scheme.name == cfg["scheme"]
    if cfg["aaq"] is None:
        assert cfg["scheme"] == "baseline_fp16"
        return
    for site, group in cfg["aaq"]["sites"].items():
        for op in ("tri_mul_out", "tri_attn_end", "pair_trans"):
            pol = scheme.cfg.policy_for(f"{op}.{site}")
            assert [pol.bits, pol.k_outliers] == cfg["aaq"]["groups"][group]


@pytest.mark.parametrize("cell", BACKLOG_CELLS)
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


def weights_digest(params) -> str:
    """sha256 of every leaf's path, dtype, shape and bytes, in tree order."""
    h = hashlib.sha256()
    for path, leaf in jax.tree.leaves_with_path(params):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", MODELS)
def test_seeds_make_the_same_weights(name):
    model, cfg = model_and_tiny(name)
    a = model.make_params(cfg, 2 ** 33 + 7)
    b = model.make_params(cfg, 2 ** 33 + 7)
    c = model.make_params(cfg, 7)
    assert weights_digest(a) == weights_digest(b)
    assert all(not np.array_equal(np.asarray(x, np.float32),
                                  np.asarray(y, np.float32))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(c))
               if np.ptp(np.asarray(x, np.float32)) > 0)


@pytest.mark.parametrize("name", MODELS)
def test_weights_have_the_servers_layout(name):
    model, cfg = model_and_tiny(name)
    mine = jax.eval_shape(lambda: model.make_params(cfg, 1))
    theirs = jax.eval_shape(lambda k: model.init_program_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_esmfold_weights_are_pinned():
    """The weights of the parent of the model seam, bit for bit: a move of
    the weight code may not change what a seed makes."""
    model, cfg = model_and_tiny("esmfold_ppm")
    assert weights_digest(model.make_params(cfg, 2 ** 33 + 7)) == (
        "d377d10ecfdbb1d6927fa4375a0f9fa5a8eb88c6f4f66ecdd98a4a83279134b5")
    assert weights_digest(model.make_params(cfg, 7)) == (
        "37b9dc726adc959465f11b9cedc87ccfeab713f0b5540e539c020dbd5a5bd36a")


def test_model_lookup():
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        model = run.model_of(cfg)
        assert model.__name__ == f"bench.models.{cfg['model']}"
        for name in ("program_config", "make_client", "make_params",
                     "Reference", "fold_flops", "init_program_params"):
            assert callable(getattr(model, name)), name
        assert (ROOT / model.TINY).is_file()
    for unknown in ("no_such_model", "../esmfold_ppm", None):
        with pytest.raises(run.Refused):
            run.model_of({"model": unknown})
    with pytest.raises(run.Refused):
        run.model_of({})


TOY_MODEL = '''"""esmfold_ppm's names under another model name, each call counted."""
from bench.models import esmfold_ppm

CALLS = []
TINY = "bench/configs/toy.json"
program_config = esmfold_ppm.program_config
init_program_params = esmfold_ppm.init_program_params


def _counted(name, f):
    def call(*args, **kwargs):
        CALLS.append(name)
        return f(*args, **kwargs)
    return call


make_params = _counted("make_params", esmfold_ppm.make_params)
make_client = _counted("make_client", esmfold_ppm.make_client)
fold_flops = _counted("fold_flops", esmfold_ppm.fold_flops)


class Reference(esmfold_ppm.Reference):
    def __call__(self, *args, **kwargs):
        CALLS.append("Reference")
        return super().__call__(*args, **kwargs)
'''

TOY_RUN = textwrap.dedent('''
    import json
    from bench import control, run
    run.use_compile_cache = lambda: None
    spec = run.load_json(run.ROOT / "BENCHMARK.json")
    w = run.cell(spec, "toy.short")
    path = run.config_file(spec, w["config"])
    cfg = run.load_json(path)
    model = run.model_of(cfg)
    out = run.run_cell(spec, "toy.short", 2 ** 32 + 5, 4.0, False)
    fold = run.Fold(0, 20, True)
    mfu = run.metric_reader("fold_mfu")(run.Run(
        cfg=cfg, peak={"bf16_flops_s": 1e12}, window_s=1.0,
        window=[run.Batch(32, 1, 0, [fold])]))
    traffic = run.load_json(run.BENCH / "traffic" / f"{w['traffic']}.json")
    ctl = control.control_readings(cfg, traffic, 2 ** 32 + 5)
    print(json.dumps({"run": run.__file__, "config": str(path),
                      "model": model.__file__, "calls": model.CALLS,
                      "correct": out["correct"], "checks": out["checks"],
                      "attempted": out["attempted"], "mfu": mfu,
                      "control": sorted(ctl)}))
''')


def digests(root: Path) -> dict[str, str]:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_second_model_by_files_alone(tmp_path):
    """A configuration of another model goes in by new files and new
    ``BENCHMARK.json`` entries only: the cell, its configuration file, its
    model module, the weights, the client, the reference of ``correct``
    and of the control, and the FLOPs of ``fold_mfu`` all resolve to it,
    and a whole run of the cell on the CPU is correct."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digests(tmp_path / "bench")

    bench = tmp_path / "bench"
    (bench / "models" / "toy_fold.py").write_text(TOY_MODEL)
    toy = dict(json.loads((bench / "testdata/tiny_aaq.json").read_text()),
               model="toy_fold", notes="a second model, for a test")
    (bench / "configs" / "toy.json").write_text(json.dumps(toy))
    shutil.copy(bench / "testdata" / "tiny_backlog.json",
                bench / "traffic" / "toy_backlog.json")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy", "source": "a test",
                            "file": "bench/configs/toy.json", "reduced": [],
                            "why": "a second fold model"})
    spec["workloads"].append({"name": "toy.short", "config": "toy",
                              "traffic": "toy_backlog", "chips": 1,
                              "why": "a second fold model"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", TOY_RUN], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["run"] == str(bench / "run.py")
    assert got["config"] == str(bench / "configs" / "toy.json")
    assert got["model"] == str(bench / "models" / "toy_fold.py")
    assert {"make_params", "make_client", "Reference",
            "fold_flops"} <= set(got["calls"])
    assert got["correct"] and got["attempted"] > 0, got["checks"]
    assert got["mfu"] > 0 and got["control"]
    after = digests(tmp_path / "bench")
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        "models/toy_fold.py", "configs/toy.json", "traffic/toy_backlog.json"}
    old = json.loads((ROOT / "BENCHMARK.json").read_text())
    new = json.loads((tmp_path / "BENCHMARK.json").read_text())
    for key in old:
        assert new[key][:len(old[key])] == old[key] if isinstance(
            old[key], list) else new[key] == old[key]


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
