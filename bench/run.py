"""Chip benchmark of the fold server: one cell, one run, one process.

    python3 bench/run.py --workload esmfold_aaq.short --seed 7 --seconds 40 --trace 0

``--workload`` names a cell of ``BENCHMARK.json``.  The run

1. refuses to start unless JAX's first device is a TPU and the machine has
   the chips the cell asks for, and unless ``bench/peaks.json`` knows the
   chip (exit 1, no result);
2. builds the cell's configuration (``bench/configs/<config>.json``) and,
   through the model module its ``"model"`` key names
   (``bench/models/<model>.py``), weights made on the device from
   ``--seed`` and the fold client that ``python -m repro.launch.serve
   --mode ppm`` serves through, with the serving settings of the cell's
   traffic mix (``bench/traffic/<traffic>.json``) and the scheme as the
   configuration states it (its AAQ site table);
3. compiles, or loads from the compile cache, every (bucket, launch size)
   the window runs: all of that is ``setup_s``;
4. serves the mix's closed backlog (``bench/traffic/<kind>.py``, whose
   requests the harness hands on untouched) for ``--seconds``;
   ``folds_per_s`` counts the folds of the batches retired in the window
   over the time to the last of them;
5. reads the chip's peak memory, frees the server, and compares a sample
   of the folds the window served with the model module's plain
   ``Reference`` (``bench/correct.py``);
6. with ``--trace 1``, traces the window with the JAX profiler and reports
   the cell's per-layer metrics instead, each read by its own module
   ``bench/metrics/<metric>.py`` from the run's record and the trace
   reduction (``bench/trace.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), and last ``checks``, each compared number with its limit.
The same numbers end standard error.

The harness is driven by data.  A later change adds a configuration, a
traffic mix or a per-layer metric by adding its files and its entries in
``BENCHMARK.json``; it edits no file that is already here.  A
configuration of another fold model adds its model module
(``bench/models/__init__.py`` lists what one defines); a new kernel's
roofline passes its own work function to ``bench.ops.kernels.
roofline_share`` from a new file under ``bench/ops/``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse         # noqa: E402
import gc               # noqa: E402
import importlib        # noqa: E402
import json             # noqa: E402
import shutil           # noqa: E402
import sys              # noqa: E402
from dataclasses import dataclass, field   # noqa: E402
from pathlib import Path                   # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
# run as a script, this directory leads sys.path, where its modules would
# shadow the standard library's (``trace``): the package root goes there
sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
    p for p in sys.path if Path(p or ".").resolve() != BENCH]
if __name__ == "__main__":
    # the modules loaded by name import this one as bench.run: one copy
    sys.modules.setdefault("bench.run", sys.modules[__name__])
TRACE_DIR = ROOT / ".bench_trace"            # listed in .gitignore
QUEUE_BATCHES = 2       # full batches kept queued in every bucket


def log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


class Refused(RuntimeError):
    """The run cannot be measured here; it prints no result."""


@dataclass(frozen=True)
class Fold:
    """What the metrics read of one served fold."""
    request_id: int
    length: int
    ok: bool


@dataclass
class Batch:
    """One served batch: its shape and what it returned."""
    bucket: int
    launched: int
    chunk: int
    results: list                   # FoldResults (Folds once slim)
    t_retire: float = 0.0           # seconds after the window opened

    def slim(self) -> "Batch":
        """The same batch without its outputs."""
        return Batch(self.bucket, self.launched, self.chunk,
                     [Fold(r.request_id, r.length, r.ok)
                      for r in self.results], self.t_retire)


@dataclass
class Run:
    """What a per-layer metric reader gets."""
    cfg: dict
    peak: dict | None
    window: list = field(default_factory=list)    # batches retired in it
    window_s: float = 0.0
    compiles: int = 0
    spans: list = field(default_factory=list)     # engine spans, window
    trace: object = None                          # bench.trace.Reduction


# --------------------------------------------------------------------------
# loading by name
# --------------------------------------------------------------------------
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise Refused(f"no workload {name!r} in BENCHMARK.json")


def config_file(spec: dict, name: str) -> Path:
    for c in spec["configs"]:
        if c["name"] == name:
            return ROOT / c["file"]
    raise Refused(f"no configuration {name!r} in BENCHMARK.json")


def traffic_generator(traffic: dict):
    return importlib.import_module(f"bench.traffic.{traffic['kind']}")


def model_of(cfg: dict):
    """The model module ``bench/models/<model>.py`` the configuration's
    ``"model"`` key names."""
    model = cfg.get("model")
    name = f"bench.models.{model}"
    if not str(model).isidentifier():
        raise Refused(f"no model {model!r}: not a module name")
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise Refused(f"no model {model!r} in bench/models/") from None


def metric_reader(name: str):
    return importlib.import_module(f"bench.metrics.{name}").read


def per_layer(spec: dict, workload: str) -> list[dict]:
    """The per-layer metrics this cell reports."""
    return [m for m in spec["per_layer"]
            if workload in m.get("workloads", [workload])]


def end_to_end(spec: dict, workload: str) -> list[dict]:
    return [m for m in spec["end_to_end"]
            if workload in m.get("workloads", [workload])]


# --------------------------------------------------------------------------
# the chip
# --------------------------------------------------------------------------
def chip(chips: int):
    """The devices of this run; Refused unless they are TPUs enough."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"no TPU: JAX's first device is "
                      f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips, found "
                      f"{len(devices)}")
    return devices


def peak_entry(kind: str) -> dict:
    peaks = load_json(BENCH / "peaks.json")
    if kind not in peaks:
        raise Refused(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def use_compile_cache() -> None:
    """JAX's persistent compilation cache in the checkout (the program's
    own placement), every program kept, so only a checkout's first run
    compiles."""
    import jax

    from repro.launch.compile_cache import place_compile_cache
    place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))


# --------------------------------------------------------------------------
# the server
# --------------------------------------------------------------------------
def program_scheme(cfg: dict):
    """The program's scheme as the configuration states it: under AAQ the
    configuration's site table (a site named by its last part), ahead of
    the program's own table for any site it does not name."""
    import dataclasses
    import re

    from repro.core import make_scheme
    from repro.core.policy import DEFAULT_SITE_TABLE, QuantPolicy
    scheme = make_scheme(cfg["scheme"])
    if cfg["aaq"] is None:
        return scheme
    groups = {g: QuantPolicy(bits, k, name=g)
              for g, (bits, k) in cfg["aaq"]["groups"].items()}
    table = tuple((rf".*\.{re.escape(site)}", groups[g])
                  for site, g in cfg["aaq"]["sites"].items())
    return dataclasses.replace(scheme, cfg=dataclasses.replace(
        scheme.cfg, site_table=table + DEFAULT_SITE_TABLE))


def serve_args(cfg: dict, traffic: dict):
    """The ``repro.launch.serve --mode ppm`` arguments of this cell."""
    from repro.launch import serve
    s = traffic["serve"]
    argv = ["--mode", "ppm", "--scheme", cfg["scheme"],
            "--kernels", s["kernels"],
            "--buckets", ",".join(str(b) for b in traffic["buckets"]),
            "--max-tokens-per-batch", str(s["max_tokens_per_batch"]),
            "--max-batch", str(s["max_batch"]),
            "--inflight-depth", str(s["inflight_depth"]),
            "--chunk-size", str(s["chunk_size"])]
    if s["mem_budget_mb"] is not None:
        argv += ["--mem-budget-mb", str(s["mem_budget_mb"])]
    if not s["fidelity"]:
        argv.append("--no-fidelity")
    return serve.build_parser().parse_args(argv)


def warm_up(client, traffic: dict) -> None:
    """Compile, or load from the cache, every (bucket, cap) executable.
    No batch is served: on the chip a window's first batch of a bucket
    takes as long after a warm batch as without one."""
    t = time.perf_counter()
    client.core.warmup(ladder=(traffic["serve"]["max_batch"],))
    log(f"compiled or loaded in {time.perf_counter() - t:.2f} s")


def serve_window(client, backlog, seconds: float):
    """Serve the backlog for ``seconds``; returns (window batches, every
    batch served, seconds to the last retire in the window, every request
    by id, as the generator made it)."""
    pending: dict[int, list] = {b: [] for b in backlog.pool}
    requests: dict[int, object] = {}

    def top_up():
        while min(len(v) for v in pending.values()) < QUEUE_BATCHES:
            bucket, batch = backlog.next_batch()
            hs = [client.submit(q) for q in batch]
            requests.update((h.request_id, q) for h, q in zip(hs, batch))
            pending[bucket].append(hs)

    top_up()
    served: list[Batch] = []
    window: list[Batch] = []
    t0 = time.perf_counter()
    t_last = 0.0

    def retire_one() -> Batch | None:
        results = client.drive(max_batches=1)
        for r in results:               # the consumer takes its distogram,
            if r.ok:                    # which frees it on the device
                r.distogram.fetch()
        if not results:
            return None
        b = Batch(results[0].bucket, results[0].launched_batch,
                  results[0].chunk_size, results, time.perf_counter() - t0)
        served.append(b)
        return b

    while True:
        b = retire_one()
        if b is None:
            raise RuntimeError("the backlog ran dry")
        pending[b.bucket].pop(0)
        if b.t_retire > seconds:
            break
        window.append(b)
        t_last = b.t_retire
        top_up()
    for hs in pending.values():            # end the backlog: cancel the
        for batch in hs:                   # queue, retire what is in flight
            for h in batch:
                h.cancel()
    while client.core.inflight_count:
        retire_one()
    return window, served, t_last, requests


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------
def run_cell(spec: dict, workload: str, seed: int, seconds: float,
             trace: bool, *, devices=None, peak=None) -> dict:
    """One run of ``workload`` of ``spec``; returns the result object.
    ``devices`` and ``peak`` come from ``chip`` and ``peak_entry``."""
    import jax

    from bench import correct

    w = cell(spec, workload)
    cfg = load_json(config_file(spec, w["config"]))
    model = model_of(cfg)
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    gen = traffic_generator(traffic)
    readers = {m["name"]: metric_reader(m["name"])
               for m in per_layer(spec, workload)}
    use_compile_cache()
    devices = devices or jax.devices()

    params = model.make_params(cfg, seed)
    jax.block_until_ready(params)
    log(f"weights at {time.perf_counter() - T_PROCESS:.2f} s")
    client = model.make_client(cfg, traffic, params)
    caps = {b: client.core.batch_for_bucket(b) for b in traffic["buckets"]}
    warm_up(client, traffic)
    log(f"warm at {time.perf_counter() - T_PROCESS:.2f} s: caps {caps}, "
        f"{client.core.compile_count} executables")
    backlog = gen.Backlog(traffic, seed, caps)
    compiles0 = client.core.compile_count
    client.tracer.reset()

    trace_dir = TRACE_DIR / str(seed)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    setup_s = time.perf_counter() - T_PROCESS
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            window, served, window_s, requests = serve_window(
                client, backlog, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    compiles = client.core.compile_count - compiles0
    for b in served:
        log(f"batch bucket={b.bucket} rows={b.launched} chunk={b.chunk} "
            f"folds={len(b.results)} retired_at={b.t_retire:.3f}")
    memory_peak = peak_bytes(devices[0])
    spans = list(client.tracer.spans)
    sample = correct.sample([[r for r in b.results if r.ok] for b in served],
                            seed, traffic["sample"])
    outputs = [(requests[r.request_id], r.bucket, r.coords,
                correct.distogram(r)) for r in sample]
    # keep only counts: the server, its outputs on the device and its
    # executables go before the reference runs
    run = Run(cfg=cfg, peak=peak,
              window=[b.slim() for b in window], window_s=window_s,
              compiles=compiles, spans=spans)
    del client, window, served, sample, backlog, requests
    gc.collect()

    t_ref = time.perf_counter()
    checks, got = correct.compare(cfg, params, outputs)
    log(f"reference {time.perf_counter() - t_ref:.2f} s over lengths "
        f"{[gen.length(o[0]) for o in outputs]}")
    failed = sum(not r.ok for b in run.window for r in b.results)
    ok = failed == 0 and bool(outputs) and all(
        c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    n_folds = sum(len(b.results) for b in run.window)
    out = {"correct": ok, "attempted": n_folds, "failed": failed}
    if trace:
        from bench.trace import reduce_profile
        run.trace = reduce_profile(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = {}
        for m in per_layer(spec, workload):
            v = readers[m["name"]](run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = run.trace.breakdown()
    else:
        ok_folds = sum(r.ok for b in run.window for r in b.results)
        values = {"folds_per_s": ok_folds / window_s if window_s else 0.0,
                  "peak_hbm_gib": memory_peak / 2 ** 30,
                  "setup_s": setup_s}
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in end_to_end(spec, workload)}
        out["device"] = device
    out["readings"] = got
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = load_json(ROOT / "BENCHMARK.json")
        w = cell(spec, args.workload)
        devices = chip(w["chips"])
        peak = peak_entry(devices[0].device_kind)
        out = run_cell(spec, args.workload, args.seed, args.seconds,
                       bool(args.trace), devices=devices[:w["chips"]],
                       peak=peak)
    except Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr)
        return 1
    for name, v in out["readings"].items():
        print(f"reading {name} {v!r}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
