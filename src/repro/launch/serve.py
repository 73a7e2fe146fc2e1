"""Serving launcher — the paper's workload class (inference).

Two services:
  * ``--mode ppm``  — protein folding through the request-lifecycle
    ``FoldClient`` (repro.serving): ``submit()`` returns handles with
    priorities (``--priority-split``) and deadlines (``--deadline-s``),
    progress streams as typed events, and batches run on the bucketed
    ``EngineCore`` — a dispatch/retire pipeline over a bounded in-flight
    ring (``--inflight-depth``), occupancy-fitted launch sizes, lazy
    distogram transfer, token-budget batching with fill-or-timeout
    (``--batch-linger-ms``), and AAQ-aware admission control — driven by
    a background thread (``--driver thread``) or the inline pump.
    ``--no-engine`` keeps the one-request-at-a-time fallback (same bucket
    padding, so both paths produce bitwise-identical real-token coords).
  * ``--mode lm``   — autoregressive decode through the SAME serving
    substrate (client/handle/event lifecycle, admission, metrics, HTTP
    transport) hosted by ``LMDecodeWorkload``: continuous per-token
    batching over ``--batch`` slots, ring KV cache of ``--window``
    positions, ``--quant-kv`` stores KV AAQ-quantized and admission
    prices requests at the scheme's KV bits-per-value
    (``--mem-budget-mb``); ``--drift-tol`` gates quantized logits
    against an fp16-KV twin.  ``--listen`` serves it over HTTP
    (``POST /v1/generate``, SSE ``token`` events).

``--kernels {pallas,ref,auto}`` selects the kernel backend for BOTH paths
(engine executables and the --no-engine fallback are lowered through
``repro.kernels.dispatch``); ``pallas`` off-TPU runs the kernels in
interpret mode.  ``--report`` rows record the backend each batch ran under.

    PYTHONPATH=src python -m repro.launch.serve --mode ppm --n 8
    PYTHONPATH=src python -m repro.launch.serve --mode ppm --n 8 \
        --max-tokens-per-batch 256 --mem-budget-mb 64 --buckets 32,64
    PYTHONPATH=src python -m repro.launch.serve --mode ppm --n 8 \
        --priority-split 0.25 --deadline-s 30 --driver thread
    PYTHONPATH=src python -m repro.launch.serve --mode ppm --kernels pallas
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.serve --mode ppm \
        --buckets 32,64 --mesh 2x4 --shard-threshold 64
    PYTHONPATH=src python -m repro.launch.serve --mode ppm \
        --buckets 1024 --chunk-size auto --mem-budget-mb 512 --no-fidelity
    PYTHONPATH=src python -m repro.launch.serve --mode ppm \
        --ppm-config esmfold_ppm --buckets 256,512,1024 --chunk-size auto \
        --mem-budget-mb 6000 --no-fidelity --min-len 100 --max-len 1000
    PYTHONPATH=src python -m repro.launch.serve --mode lm --arch qwen1.5-0.5b
    PYTHONPATH=src python -m repro.launch.serve --mode lm --n 8 \
        --quant-kv --mem-budget-mb 4 --drift-tol 0.1
    PYTHONPATH=src python -m repro.launch.serve --mode lm \
        --listen 127.0.0.1:0 --replicas 2 --quant-kv

``--ppm-config`` names the fold model: ``reduced`` (the default, a
2-block toy the CPU tests use) or ``esmfold_ppm`` (the published widths:
48 blocks, Hm 1024, Hz 128, bf16).  Parameters are random, from a fixed
seed.  The process exits 1 when any request ended FAILED or the
background driver recorded an error.  Compiled executables persist under
``JAX_COMPILATION_CACHE_DIR`` (or ``.jax_cache`` in the checkout).

``--listen HOST:PORT`` switches ppm mode into a network server: an HTTP
front-end (``POST /v1/fold``, status/SSE/cancel, ``/metrics``) over a
``--replicas``-wide fleet of engine replicas routed on live telemetry
(repro.serving.transport); port 0 binds ephemerally and the bound address
is printed as ``# listening ...``:

    PYTHONPATH=src python -m repro.launch.serve --mode ppm \
        --listen 127.0.0.1:8077 --replicas 2 --no-fidelity
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import (get_config, get_ppm_config, reduce_config,
                           reduce_ppm_config)
from repro.core import make_scheme
from repro.kernels import dispatch
from repro.data.pipeline import ProteinSampler
from repro.launch.compile_cache import place_compile_cache
from repro.models import lm
from repro.models.ppm import init_ppm, ppm_forward, tm_score
from repro.serving import (CSV_HEADER, LM_CSV_HEADER, FleetRouter,
                           FoldClient, FoldHTTPServer, LMClient,
                           MetricsServer, calibrate, csv_row, install_floors,
                           jax_profile, lm_csv_row, load_cost_table,
                           make_serving_mesh, pad_to_bucket, parse_buckets,
                           parse_chunk_spec)
from repro.serving.observability.httpd import parse_hostport


PPM_CONFIGS = {"reduced": reduce_ppm_config, "esmfold_ppm": get_ppm_config}


def ppm_model(name: str, seed: int = 0):
    """(cfg, params) of the named fold model, params random from ``seed``."""
    cfg = PPM_CONFIGS[name]()
    params = jax.jit(init_ppm, static_argnums=1)(jax.random.PRNGKey(seed),
                                                 cfg)
    return cfg, params


def fold_client(args, cfg, params, buckets, *, mesh=None,
                cost_model=None) -> FoldClient:
    """The FoldClient ``--mode ppm`` serves through, built from the CLI
    args (shared by inline serving, every HTTP replica and chip_smoke)."""
    return FoldClient(
        params, cfg, args.scheme, buckets=buckets,
        max_tokens_per_batch=args.max_tokens_per_batch,
        max_batch=args.max_batch, mem_budget_mb=args.mem_budget_mb,
        fidelity=not args.no_fidelity, kernels=args.kernels,
        mesh=mesh, shard_threshold=args.shard_threshold,
        inflight_depth=args.inflight_depth,
        linger_ms=args.batch_linger_ms,
        adaptive_linger=not args.no_adaptive_linger,
        chunk_size=args.chunk_size, cost_model=cost_model)


def _sample_trace(args) -> list[np.ndarray]:
    sampler = ProteinSampler(seed=11, min_len=args.min_len,
                             max_len=args.max_len)
    return [sampler.sample(i) for i in range(args.n)]


def priority_tiers(n: int, split: float) -> list[int]:
    """Deterministic two-tier assignment: a ``split`` fraction of requests
    (interleaved, not front-loaded) get priority 1, the rest 0."""
    split = min(max(split, 0.0), 1.0)
    return [1 if int((i + 1) * split) > int(i * split) else 0
            for i in range(n)]


def _serve_ppm_sequential(args, cfg, params, seqs, buckets) -> int:
    """Fallback path: one request at a time, but properly bucketed+jitted —
    the jitted forward is actually *called* (the old demo loop built ``fwd``
    and then bypassed it, re-tracing every request) and requests are padded
    to bucket edges so XLA compiles once per bucket, not once per length.
    Honors ``--kernels``: both jitted forwards trace under the selected
    dispatch backend (set process-wide in ``main``)."""
    scheme = make_scheme(args.scheme)
    backend = dispatch.describe(args.kernels)
    fwd = jax.jit(lambda p, a, m: ppm_forward(p, a, cfg, scheme, mask=m))
    fwd_fp = None
    if not args.no_fidelity:
        fwd_fp = jax.jit(lambda p, a, m: ppm_forward(p, a, cfg, mask=m))
    print("request,len,bucket,latency_ms,tm_vs_fp,kernel_backend")
    for i, seq in enumerate(seqs):
        bucket = next((b for b in buckets if len(seq) <= b), None)
        if bucket is None:
            print(f"{i},{len(seq)},,rejected:too-long,,")
            continue
        aat, mask = pad_to_bucket([seq], bucket)
        aat, mask = jnp.asarray(aat), jnp.asarray(mask)
        t0 = time.perf_counter()
        out = fwd(params, aat, mask)
        jax.block_until_ready(out["coords"])
        ms = (time.perf_counter() - t0) * 1e3
        tm = ""
        if fwd_fp is not None:
            out_fp = fwd_fp(params, aat, mask)
            tm = f"{float(tm_score(out['coords'][0, :len(seq)], out_fp['coords'][0, :len(seq)])):.4f}"
        print(f"{i},{len(seq)},{bucket},{ms:.1f},{tm},{backend}")
    return 0


def serve_http(args, cfg, params, buckets) -> int:
    """Network server mode (``--listen``): a FoldHTTPServer over a
    ``--replicas``-wide FleetRouter, up until SIGTERM/SIGINT (or
    ``--serve-for-s``).  Each replica is its own FoldClient + background
    driver; the router balances on live queue-depth/in-flight telemetry
    scraped from the replicas' registries."""
    import signal
    import threading

    try:
        host, port = parse_hostport(args.listen)
        if args.cost_table:
            load_cost_table(args.cost_table)   # fail loudly before binding
    except (ValueError, FileNotFoundError) as e:
        print(f"error: {e}")
        return 2

    def factory(i: int) -> FoldClient:
        # each replica binds its own copy of the persisted cost table (a
        # CostModel is bound to exactly one core); floors install once,
        # process-wide
        cost_model = (load_cost_table(args.cost_table)
                      if args.cost_table else None)
        client = fold_client(args, cfg, params, buckets,
                             mesh=make_serving_mesh(args.mesh),
                             cost_model=cost_model)
        client.tracer.set_metadata(
            replica=i, scheme=args.scheme,
            kernels=dispatch.describe(args.kernels), buckets=list(buckets),
            inflight_depth=args.inflight_depth,
            **client.core.placement.describe(),
            **client.core.chunk.describe())
        if cost_model is not None:
            install_floors(cost_model)
            client.core.warmup_from_table()
        if args.warmup:
            client.warmup()
        return client

    router = FleetRouter(factory, args.replicas,
                         max_restarts=args.max_restarts)
    server = FoldHTTPServer(router, port=port, host=host).start()
    # the CI job and any launcher scrape THIS line for the bound address
    # (--listen HOST:0 binds an ephemeral port)
    print(f"# listening {server.url} replicas={args.replicas} "
          f"buckets={','.join(map(str, buckets))} "
          f"kernels={dispatch.describe(args.kernels)}", flush=True)

    done = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: done.set())
    try:
        done.wait(args.serve_for_s if args.serve_for_s > 0 else None)
    except KeyboardInterrupt:
        pass
    print("# shutting down", flush=True)
    server.stop()
    router.stop(drain=True)
    for r in router.replicas:
        s = r.client.metrics.summary()
        print(f"# replica={r.index} served={s['served']}/{s['requests']} "
              f"rejected={s['rejected']} expired={s['expired']} "
              f"cancelled={s['cancelled']} compiles={s['compiles']}")
    if args.trace_out:
        stem = args.trace_out[:-5] if args.trace_out.endswith(".json") \
            else args.trace_out
        for path in router.save_traces(stem):
            print(f"# trace -> {path}")
    print("# fleet shutdown complete", flush=True)
    return 0


def serve_ppm(args):
    cfg, params = ppm_model(args.ppm_config)
    seqs = _sample_trace(args)
    try:
        buckets = parse_buckets(args.buckets, args.min_len, args.max_len)
    except ValueError:
        print(f"error: --buckets must be 'pow2' or comma-separated ints, "
              f"got {args.buckets!r}")
        return 2
    try:
        parse_chunk_spec(args.chunk_size)
    except ValueError as e:
        print(f"error: {e}")
        return 2
    if args.no_engine:
        return _serve_ppm_sequential(args, cfg, params, seqs, buckets)

    if (args.mesh is None) != (args.shard_threshold is None):
        print("error: --mesh and --shard-threshold must be given together "
              "(one without the other shards nothing)")
        return 2
    try:
        mesh = make_serving_mesh(args.mesh)
    except ValueError as e:
        print(f"error: {e}")
        return 2
    if args.calibrate and args.listen is not None:
        print("error: --calibrate is an inline warmup mode; run it without "
              "--listen, then point the server at the table with "
              "--cost-table")
        return 2
    if args.listen is not None:
        return serve_http(args, cfg, params, buckets)
    # measured cost model: --cost-table PATH reloads a persisted table so
    # this restart starts smart; --calibrate (re)builds it in place
    cost_model = None
    if args.cost_table and not args.calibrate:
        try:
            cost_model = load_cost_table(args.cost_table)
        except (FileNotFoundError, ValueError) as e:
            print(f"error: {e}")
            return 2
    client = fold_client(args, cfg, params, buckets, mesh=mesh,
                         cost_model=cost_model)
    client.tracer.set_metadata(
        scheme=args.scheme, kernels=dispatch.describe(args.kernels),
        buckets=list(buckets), inflight_depth=args.inflight_depth,
        **client.core.placement.describe(),
        **client.core.chunk.describe())
    server = None
    if args.metrics_port is not None:
        server = MetricsServer(client, port=args.metrics_port).start()
        print(f"# metrics endpoint {server.url}/metrics")
    cm = client.core.cost_model
    if args.calibrate:
        # replay every cached executable with fake data, record measured
        # latencies (median-of-k, warm, engine clock), persist below
        calibrate(client.core)
        install_floors(cm)
        print(f"# calibrated entries={cm.entry_count} "
              f"floors={cm.floors.get('flash_seq')}/"
              f"{cm.floors.get('qmm_tokens')} "
              f"({cm.floors.get('source')})", flush=True)
    elif cost_model is not None:
        install_floors(cm)
        warmed = client.core.warmup_from_table()
        print(f"# cost table loaded {args.cost_table} "
              f"entries={cm.entry_count} calibrated={cm.calibrated_count} "
              f"warmed={warmed} executables", flush=True)
    if args.warmup:
        client.warmup()
    client.metrics.record_cost_table(cm.entry_count, cm.calibrated_count,
                                     cm.age_s())
    # everything the table (or static warmup) pre-compiled is warm; the
    # steady-state contract is that serving adds ZERO compiles on top
    warm_compiles = client.core.compile_count
    tiers = priority_tiers(len(seqs), args.priority_split)
    t0 = time.perf_counter()
    with jax_profile(args.jax_profile):
        if args.driver == "thread":
            client.start()
        handles = [client.submit(s, priority=p, deadline_s=args.deadline_s)
                   for s, p in zip(seqs, tiers)]
        if args.driver == "thread":
            for h in handles:
                if not h.done:
                    h.result(timeout=600.0)
            client.stop()
        else:
            client.drive()
    client.metrics.wall_s = time.perf_counter() - t0
    results = sorted(client.metrics.results, key=lambda r: r.request_id)
    print(CSV_HEADER)
    for r in results:
        print(csv_row(r))
    s = client.metrics.summary()
    placements = sorted({r.placement for r in results if r.ok})
    chunks = sorted({r.chunk_size for r in results if r.ok})
    print(f"# served={s['served']}/{s['requests']} "
          f"rejected={s['rejected']} expired={s['expired']} "
          f"compiles={s['compiles']} "
          f"req/s={s['requests_per_s']:.2f} tok/s={s['tokens_per_s']:.1f} "
          f"kernels={dispatch.describe(args.kernels)} "
          f"placements={'/'.join(placements) or 'none'} "
          f"chunks={'/'.join(str(c) for c in chunks) or 'none'} "
          f"max_est_act_mb={s['max_est_act_mb']:.1f} "
          f"failed={s['failed']} driver_errors={len(client.driver_errors)}"
          + (f" budget_mb={args.mem_budget_mb:.1f}"
             if args.mem_budget_mb else ""))
    print(f"# queue_wait_ms p50={s['queue_wait_ms']['p50']:.1f} "
          f"p95={s['queue_wait_ms']['p95']:.1f} "
          f"p99={s['queue_wait_ms']['p99']:.1f} "
          f"| run_ms p50={s['run_ms']['p50']:.1f} "
          f"p95={s['run_ms']['p95']:.1f} p99={s['run_ms']['p99']:.1f}")
    p = s["pipeline"]
    print(f"# pipeline inflight_depth={p['inflight_depth']} "
          f"max_inflight={p['max_inflight']} batches={p['batches']} "
          f"mean_occupancy={p['mean_batch_occupancy']:.3f} "
          f"linger_ms={p['linger_ms']:.0f} linger_holds={p['linger_holds']}")
    c = s["cost_model"]
    print(f"# cost_model entries={c['table_entries']} "
          f"calibrated={c['table_calibrated']} "
          f"predictions={c['predictions']} "
          f"pred_err_p50={c['prediction_error']['p50']:.2f} "
          f"bad_holds={c['linger_bad_holds']} "
          f"infeasible={sum(c['infeasible'].values())} "
          f"adaptive_linger={'off' if args.no_adaptive_linger else 'on'} "
          f"post_warmup_compiles={client.core.compile_count - warm_compiles}")
    if args.calibrate:
        # persisted AFTER serving so launch sizes discovered by the live
        # trace ride along — a --cost-table restart warms the WHOLE set
        path = args.cost_table or "cost_table.json"
        cm.save(path)
        print(f"# cost table -> {path} entries={cm.entry_count} "
              f"calibrated={cm.calibrated_count}")
    for b in s["buckets"]:
        print(f"# bucket={b['bucket']} n={b['requests']} "
              f"compiles={b['compiles']} wait_ms={b['mean_queue_wait_ms']:.1f} "
              f"run_ms={b['mean_run_ms']:.1f} waste={b['padding_waste']:.2f}")
    if args.report:
        client.metrics.save(args.report)
        print(f"# report -> {args.report}")
    if args.trace_out:
        from repro.serving import pipeline_overlaps
        client.save_trace(args.trace_out)
        print(f"# trace -> {args.trace_out} "
              f"(pipeline_overlaps={pipeline_overlaps(client.tracer)})")
    if server is not None:
        # hold the scrape endpoint open (CI polls for this marker, then
        # curls /metrics before the process exits)
        if args.metrics_hold_s > 0:
            print(f"# metrics endpoint holding {args.metrics_hold_s:.0f}s "
                  f"at {server.url}/metrics", flush=True)
            time.sleep(args.metrics_hold_s)
        server.stop()
    return 1 if s["failed"] or client.driver_errors else 0


def _lm_prompts(args, cfg) -> list[np.ndarray]:
    """Deterministic synthetic prompt trace (seeded like _sample_trace)."""
    rng = np.random.default_rng(11)
    out = []
    for _ in range(args.n):
        plen = int(rng.integers(4, max(args.prompt_len, 4) + 1))
        out.append(rng.integers(0, cfg.vocab, size=plen).astype(np.int32))
    return out


def serve_lm_http(args, cfg, params) -> int:
    """``--mode lm --listen``: the SAME HTTP front-end + fleet router as
    the fold path, but each replica is an ``LMClient`` — the substrate
    refactor's point.  ``POST /v1/generate`` submits, tokens stream as SSE
    ``token`` events, ``/metrics`` carries ``workload="lm"`` series."""
    import signal
    import threading

    try:
        host, port = parse_hostport(args.listen)
    except ValueError as e:
        print(f"error: {e}")
        return 2
    scheme = "lightnobel_aaq" if args.quant_kv else "baseline_fp16"

    def factory(i: int) -> LMClient:
        client = LMClient(params, cfg, scheme, window=args.window,
                          max_slots=args.batch,
                          mem_budget_mb=args.mem_budget_mb,
                          kernels=args.kernels,
                          default_max_new_tokens=args.tokens)
        client.tracer.set_metadata(
            replica=i, workload="lm", arch=args.arch, scheme=scheme,
            window=args.window, max_slots=args.batch,
            kernels=dispatch.describe(args.kernels))
        if args.warmup:
            client.warmup()
        return client

    router = FleetRouter(factory, args.replicas,
                         max_restarts=args.max_restarts)
    server = FoldHTTPServer(router, port=port, host=host).start()
    # the CI job and any launcher scrape THIS line for the bound address
    print(f"# listening {server.url} workload=lm replicas={args.replicas} "
          f"arch={args.arch} scheme={scheme} window={args.window} "
          f"slots={args.batch} kernels={dispatch.describe(args.kernels)}",
          flush=True)

    done = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: done.set())
    try:
        done.wait(args.serve_for_s if args.serve_for_s > 0 else None)
    except KeyboardInterrupt:
        pass
    print("# shutting down", flush=True)
    server.stop()
    router.stop(drain=True)
    for r in router.replicas:
        s = r.client.metrics.summary()
        print(f"# replica={r.index} served={s['served']}/{s['requests']} "
              f"rejected={s['rejected']} expired={s['expired']} "
              f"cancelled={s['cancelled']} tokens={s['tokens']} "
              f"restarts={r.restarts}")
    if args.trace_out:
        stem = args.trace_out[:-5] if args.trace_out.endswith(".json") \
            else args.trace_out
        for path in router.save_traces(stem):
            print(f"# trace -> {path}")
    print("# fleet shutdown complete", flush=True)
    return 0


def serve_lm(args):
    """LM decode through the serving substrate: the same client/engine/
    admission/event lifecycle as folding, hosted by ``LMDecodeWorkload``
    — continuous per-token batching over ``--batch`` slots with the KV
    cache AAQ-quantized when ``--quant-kv`` is set (admission then prices
    requests at the scheme's KV bits-per-value)."""
    cfg = reduce_config(get_config(args.arch)).replace(dtype="float32")
    if cfg.kind != "dense":
        print(f"error: --mode lm serves dense decoder archs through the "
              f"substrate; {args.arch!r} is kind={cfg.kind!r}")
        return 2
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    if args.listen is not None:
        return serve_lm_http(args, cfg, params)

    scheme = "lightnobel_aaq" if args.quant_kv else "baseline_fp16"
    client = LMClient(params, cfg, scheme, window=args.window,
                      max_slots=args.batch,
                      mem_budget_mb=args.mem_budget_mb,
                      kernels=args.kernels,
                      default_max_new_tokens=args.tokens)
    client.tracer.set_metadata(workload="lm", arch=args.arch, scheme=scheme,
                               window=args.window, max_slots=args.batch,
                               kernels=dispatch.describe(args.kernels))
    if args.warmup:
        client.warmup()
    prompts = _lm_prompts(args, cfg)
    tiers = priority_tiers(len(prompts), args.priority_split)
    t0 = time.perf_counter()
    if args.driver == "thread":
        client.start()
        handles = [client.submit(p, priority=pr, deadline_s=args.deadline_s)
                   for p, pr in zip(prompts, tiers)]
        for h in handles:
            if not h.done:
                h.result(timeout=600.0)
        client.stop()
    else:
        for p, pr in zip(prompts, tiers):
            client.submit(p, priority=pr, deadline_s=args.deadline_s)
        client.drive()
    client.metrics.wall_s = time.perf_counter() - t0
    results = sorted(client.metrics.results, key=lambda r: r.request_id)
    print(LM_CSV_HEADER)
    for r in results:
        print(lm_csv_row(r))
    s = client.metrics.summary()
    adm = client.core.admission
    print(f"# workload=lm arch={args.arch} scheme={scheme} "
          f"served={s['served']}/{s['requests']} rejected={s['rejected']} "
          f"expired={s['expired']} failed={s['failed']} "
          f"driver_errors={len(client.driver_errors)} tokens={s['tokens']} "
          f"tok/s={s['tokens_per_s']:.1f} compiles={s['compiles']} "
          f"kv_bits_per_value={adm.bits_per_value:.1f} "
          f"kv_bytes_per_req={adm.bytes_per_request} "
          f"kernels={dispatch.describe(args.kernels)}"
          + (f" budget_mb={args.mem_budget_mb:.1f}"
             if args.mem_budget_mb else ""))
    print(f"# queue_wait_ms p50={s['queue_wait_ms']['p50']:.1f} "
          f"p95={s['queue_wait_ms']['p95']:.1f} "
          f"| run_ms p50={s['run_ms']['p50']:.1f} "
          f"p95={s['run_ms']['p95']:.1f}")
    if args.report:
        client.metrics.save(args.report)
        print(f"# report -> {args.report}")
    if args.trace_out:
        client.save_trace(args.trace_out)
        print(f"# trace -> {args.trace_out}")

    if args.quant_kv and args.drift_tol is not None:
        # fp16 twin on the same prompts: the quantized-KV run must stay
        # within --drift-tol of it on first-generated-token logits
        twin = LMClient(params, cfg, "baseline_fp16", window=args.window,
                        max_slots=args.batch, kernels=args.kernels,
                        default_max_new_tokens=args.tokens)
        ref = {r.request_id: r for r in twin.run(prompts)}
        drift = max((float(np.max(np.abs(r.logits_first
                                         - ref[i].logits_first)))
                     for i, r in enumerate(results)
                     if r.ok and ref[i].ok and r.logits_first is not None),
                    default=0.0)
        ok = drift <= args.drift_tol
        print(f"# kv_drift max|logits_first - fp16|={drift:.4e} "
              f"tol={args.drift_tol:.4e} {'OK' if ok else 'FAIL'}")
        if not ok:
            return 1
    return 1 if s["failed"] or client.driver_errors else 0


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["ppm", "lm"], default="ppm")
    ap.add_argument("--ppm-config", choices=sorted(PPM_CONFIGS),
                    default="reduced",
                    help="ppm: fold model widths; 'esmfold_ppm' is the "
                         "published 48-block model, 'reduced' a 2-block toy")
    ap.add_argument("--scheme", default="lightnobel_aaq")
    ap.add_argument("--kernels", choices=list(dispatch.BACKENDS),
                    default=dispatch.AUTO,
                    help="kernel backend: Pallas flash/AAQ kernels, XLA "
                         "refs, or auto (capability + shape heuristics); "
                         "'pallas' off-TPU runs in interpret mode")
    ap.add_argument("--n", type=int, default=4)
    ap.add_argument("--min-len", type=int, default=24)
    ap.add_argument("--max-len", type=int, default=64)
    # -- ppm engine flags --
    ap.add_argument("--no-engine", action="store_true",
                    help="sequential fallback (no batching engine)")
    ap.add_argument("--no-fidelity", action="store_true",
                    help="skip the FP16-reference TM-score pass")
    ap.add_argument("--buckets", default="pow2",
                    help="'pow2' or comma-separated edges, e.g. '32,64,96'")
    ap.add_argument("--max-tokens-per-batch", type=int, default=1024)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--mem-budget-mb", type=float, default=None,
                    help="peak-activation budget for admission control "
                         "(per device when --mesh shards a bucket)")
    ap.add_argument("--mesh", default=None,
                    help="serving device mesh 'DxM' (data x model), e.g. "
                         "'2x4'; big buckets shard the pair representation "
                         "over the model axis (see --shard-threshold)")
    ap.add_argument("--shard-threshold", type=int, default=None,
                    help="buckets >= this length run mesh-sharded over the "
                         "model axis; smaller buckets stay single-device "
                         "(requires --mesh)")
    ap.add_argument("--chunk-size", default="off", metavar="{off,auto,N}",
                    help="long-fold chunked trunk execution: 'off' (default) "
                         "runs the unchunked pair stack, an integer N runs "
                         "row-chunked scans with that chunk on buckets > N, "
                         "and 'auto' lets the memory planner pick the "
                         "largest chunk per bucket that fits "
                         "--mem-budget-mb (falling back to unchunked when "
                         "the full slab already fits)")
    ap.add_argument("--warmup", action="store_true",
                    help="pre-compile every bucket at its launch cap; "
                         "occupancy-fitted sizes below the cap still "
                         "compile on their first appearance")
    ap.add_argument("--inflight-depth", type=int, default=2,
                    help="bounded dispatch/retire pipeline depth: batches "
                         "launched but not yet retired (1 = synchronous; "
                         "results are bitwise-identical at any depth)")
    ap.add_argument("--batch-linger-ms", type=float, default=0.0,
                    help="fill-or-timeout CAP: hold an underfull batch up "
                         "to this long past its most urgent arrival so "
                         "same-bucket requests can fill its dummy rows (0 "
                         "= launch immediately); inside the cap the "
                         "adaptive policy prices each hold in measured ms "
                         "(see --no-adaptive-linger)")
    ap.add_argument("--no-adaptive-linger", action="store_true",
                    help="disable arrival-rate-driven linger pricing and "
                         "hold underfull batches for the full fixed "
                         "--batch-linger-ms budget")
    ap.add_argument("--calibrate", action="store_true",
                    help="calibration warmup: replay every cached "
                         "executable (bucket, launch_batch, scheme, "
                         "placement, chunk) with fake data, record real "
                         "median-of-k latencies into the cost model, and "
                         "persist the provenance-stamped table to "
                         "--cost-table (default cost_table.json) after "
                         "serving")
    ap.add_argument("--cost-table", default=None, metavar="PATH",
                    help="persisted cost-table JSON: with --calibrate, "
                         "where to write it; without, load it so this "
                         "restart starts smart (table keys pre-compile, "
                         "calibrated dispatch floors install, scheduling "
                         "decisions are priced in measured ms)")
    ap.add_argument("--priority-split", type=float, default=0.0,
                    help="fraction of requests submitted at priority 1 "
                         "(interleaved); the rest run at priority 0")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request queue deadline; requests still "
                         "queued past it expire instead of running")
    # -- network serving (HTTP front-end + fleet) --
    ap.add_argument("--listen", default=None, metavar="HOST:PORT",
                    help="serve the fold API over HTTP on this address "
                         "(port 0 = ephemeral; the bound address is "
                         "printed as '# listening ...'); ignores --n and "
                         "runs until SIGTERM/--serve-for-s")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the HTTP front-end; the "
                         "router balances on live queue-depth/in-flight "
                         "telemetry from each replica's registry")
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="per-replica restart budget: a replica whose "
                         "driver dies is rebuilt (fresh client + driver) "
                         "at most this many times; its queued requests "
                         "requeue under their original ids (0 = mark dead "
                         "and drain, never revive)")
    ap.add_argument("--serve-for-s", type=float, default=0.0,
                    help="with --listen: exit after this many seconds "
                         "(0 = run until SIGTERM/SIGINT)")
    ap.add_argument("--driver", choices=["inline", "thread"],
                    default="inline",
                    help="pump the client inline after submitting, or on "
                         "the background driver thread (async submit)")
    ap.add_argument("--report", default=None,
                    help="write per-request metrics to this .csv/.json path")
    # -- observability --
    ap.add_argument("--trace-out", default=None,
                    help="write the span trace as Chrome-trace/Perfetto "
                         "JSON to this path (open at ui.perfetto.dev)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus /metrics (+ /metrics.json, "
                         "/healthz) on this port (0 = ephemeral)")
    ap.add_argument("--metrics-hold-s", type=float, default=0.0,
                    help="keep the --metrics-port endpoint up this long "
                         "after serving finishes (lets a scraper collect "
                         "final values; CI uses this)")
    ap.add_argument("--jax-profile", default=None, metavar="DIR",
                    help="capture a JAX/XLA profiler trace into DIR "
                         "(TensorBoard/Perfetto); engine batch phases "
                         "appear as named host ranges")
    # -- lm mode (decode through the substrate) --
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--batch", type=int, default=4,
                    help="lm: decode slots (the continuous batch width)")
    ap.add_argument("--tokens", type=int, default=32,
                    help="lm: default max_new_tokens per request")
    ap.add_argument("--quant-kv", action="store_true",
                    help="lm: AAQ-quantize the KV cache (scheme "
                         "lightnobel_aaq; admission prices requests at "
                         "the scheme's KV bits-per-value)")
    ap.add_argument("--window", type=int, default=128,
                    help="lm: ring KV window (prompt+generation must fit)")
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="lm: max synthetic prompt length (lengths drawn "
                         "in [4, this])")
    ap.add_argument("--drift-tol", type=float, default=None,
                    help="lm + --quant-kv: run an fp16-KV twin on the "
                         "same prompts and exit 1 if max first-token "
                         "logit drift exceeds this")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    place_compile_cache()
    dispatch.set_backend(args.kernels)   # both modes, both ppm paths
    return serve_ppm(args) if args.mode == "ppm" else serve_lm(args)


if __name__ == "__main__":
    raise SystemExit(main())
