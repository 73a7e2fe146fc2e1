"""The control of ``correct``: the reference one precision below the served.

    python3 bench/control.py --workload esmfold_aaq.short --seeds 11,12,13

For each seed it makes the cell's weights and backlog as a run does, folds a
sample of the backlog's first batches (the longest and ``sample - 1``
others) with the model's plain reference (``bench/models/<model>.py``) in
float32 and again in float8 (e4m3), the precision below the
configuration's bfloat16, and prints each seed's numbers
(``bench/correct.py``) as one JSON line.  A limit holds only if the
control's numbers break it.  Benchmark runs never run this; it runs on the
chip at the cell's own size, and ``bench/test_bench_correct.py`` runs it at
a test size.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CONTROL_DTYPE = "float8_e4m3fn"


def backlog_sample(traffic: dict, seed: int, batches: int = 8):
    """(request, bucket) of the folds a run's sample would draw: from the
    backlog's first ``batches`` batches, at the launch sizes the token
    budget gives."""
    from bench import correct
    from bench.run import Fold, traffic_generator
    s = traffic["serve"]
    caps = {b: min(s["max_batch"], max(1, s["max_tokens_per_batch"] // b))
            for b in traffic["buckets"]}
    gen = traffic_generator(traffic)
    backlog = gen.Backlog(traffic, seed, caps)
    requests, rows = [], []
    for _ in range(batches):
        batch = backlog.next_batch()[1]
        rows.append([Fold(len(requests) + i, gen.length(q), True)
                     for i, q in enumerate(batch)])
        requests += batch
    buckets = traffic["buckets"]
    return [(requests[f.request_id],
             next(b for b in buckets if f.length <= b))
            for f in correct.sample(rows, seed, traffic["sample"])]


def control_readings(cfg: dict, traffic: dict, seed: int,
                     dtype: str = CONTROL_DTYPE) -> dict:
    """The numbers of the control (the reference in ``dtype``) against the
    float32 reference, worst over the sample."""
    from bench import correct
    from bench.run import model_of
    model = model_of(cfg)
    params = model.make_params(cfg, seed)
    ref, ctl = model.Reference(cfg), model.Reference(cfg, dtype)
    per_fold = []
    for request, bucket in backlog_sample(traffic, seed):
        rc, rd = ref(params, request, bucket)
        cc, cd = ctl(params, request, bucket)
        per_fold.append(correct.readings(cc, cd, rc, rd))
    return correct.worst(per_fold)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench"]
    from bench import run
    spec = run.load_json(ROOT / "BENCHMARK.json")
    try:
        w = run.cell(spec, args.workload)
        run.chip(w["chips"])
    except run.Refused as e:
        print(f"control: refused: {e}", file=sys.stderr)
        return 1
    run.use_compile_cache()
    cfg = run.load_json(run.config_file(spec, w["config"]))
    traffic = run.load_json(run.BENCH / "traffic" / f"{w['traffic']}.json")
    for seed in (int(s) for s in args.seeds.split(",")):
        got = control_readings(cfg, traffic, seed)
        limits = cfg.get("limits", {})
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": got,
                          "fails": [k for k, v in limits.items()
                                    if got[k] > v]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
