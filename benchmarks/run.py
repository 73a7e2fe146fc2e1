"""Benchmark entry point: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--only latency,serving]
        [--out BENCH_nightly.json] [--kernels {pallas,ref,auto}]

``--only`` filters the suites (nightly CI runs latency + serving only);
``--out`` additionally writes every emitted row as JSON — the artifact the
nightly workflow uploads so the perf trajectory is tracked per commit —
and, when the serving suite ran, the repo-root ``BENCH_serving.json``
(engine-vs-client throughput + latency percentiles) uploaded alongside it.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names (footprint, accuracy, "
                         "peak_memory, compute_cost, latency, serving, "
                         "transport, longfold)")
    ap.add_argument("--out", default=None,
                    help="also write emitted rows to this JSON path")
    ap.add_argument("--kernels", choices=["pallas", "ref", "auto"],
                    default="auto", help="kernel backend for every suite")
    args = ap.parse_args(argv)

    from repro.kernels import dispatch
    from repro.launch.compile_cache import place_compile_cache
    place_compile_cache()
    dispatch.set_backend(args.kernels)

    print("name,us_per_call,derived")
    from benchmarks import (accuracy, common, compute_cost, footprint,
                            latency, peak_memory, serving, transport)
    suites = (
        ("footprint", footprint, "Table 1 (memory footprint)", None),
        ("accuracy", accuracy, "Fig 13 (TM-score) + §4.1 RMSE", None),
        ("peak_memory", peak_memory, "Fig 15 (peak memory)", None),
        ("compute_cost", compute_cost, "Fig 16a (compute cost)", None),
        ("latency", latency, "Fig 14 (latency scaling)", None),
        ("serving", serving, "serving throughput (engine vs sequential)",
         ["--n", "8", "--max-len", "48", "--kernels", args.kernels,
          "--trace-out", "BENCH_serving_trace.json"]),
        ("transport", transport, "HTTP front-end overhead (vs in-process)",
         ["--n", "6", "--max-len", "48", "--kernels", args.kernels]),
        ("longfold", peak_memory,
         "long-fold max-N frontier (chunked admission curve)",
         ["--curve", "--out", "BENCH_longfold.json"]),
    )
    selected = (None if args.only is None
                else {s.strip() for s in args.only.split(",") if s.strip()})
    if selected is not None:
        unknown = selected - {name for name, *_ in suites}
        if unknown:
            print(f"error: unknown suites {sorted(unknown)}")
            sys.exit(2)
    serving_summary = None
    for name, mod, label, suite_argv in suites:
        if selected is not None and name not in selected:
            continue
        print(f"# --- {label} ---", flush=True)
        try:
            ret = (mod.main(suite_argv) if suite_argv is not None
                   else mod.main())
            if name == "serving" and isinstance(ret, dict):
                serving_summary = ret
        except Exception as e:                      # pragma: no cover
            traceback.print_exc()
            print(f"{mod.__name__},0,ERROR:{e}")
            sys.exit(1)
    if args.out:
        prov = common.provenance()
        with open(args.out, "w") as fh:
            json.dump({
                "kernels": dispatch.describe(args.kernels),
                "provenance": prov,
                "rows": [{"name": n, "us_per_call": us, "derived": d}
                         for n, us, d in common.ROWS],
            }, fh, indent=2)
        print(f"# rows -> {args.out}", flush=True)
        if serving_summary is not None:
            # repo-root artifact: the serving trajectory the nightly job
            # uploads (engine-vs-client throughput + p99 tails per commit)
            serving_summary.setdefault("provenance", prov)
            with open("BENCH_serving.json", "w") as fh:
                json.dump(serving_summary, fh, indent=2)
            print("# serving summary -> BENCH_serving.json", flush=True)


if __name__ == "__main__":
    main()
