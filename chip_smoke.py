"""Bring-up smoke test: fold serving on a TPU at esmfold_ppm widths.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # the mesh-sharded long bucket, 1x4

One process does everything that touches the chip.  The default run serves
seeded requests of 100-1,000 residues through the same ``FoldClient`` that
``python -m repro.launch.serve --mode ppm --ppm-config esmfold_ppm`` builds
(scheme ``lightnobel_aaq``, ``--kernels auto``, buckets up to 1,024,
``--chunk-size auto`` under a memory budget), then checks every request:

  * DONE, under a ``pallas`` / ``auto:pallas`` backend label, with finite
    coords and no driver errors;
  * TM-score >= 0.995 against a plain ``jax.jit(ppm_forward)`` of the same
    scheme under ``kernels="ref"`` on the same chip, and for one request
    served under the FP scheme, coords allclose to its ref forward.

``--four-chips`` serves a 512 bucket with ``--mesh 1x4 --shard-threshold
512``, compares it with the same requests served on one chip, and prints
each device's peak memory.  Parameters and sequences are random, from a
fixed seed.

The run exits nonzero, and prints no result, unless JAX's first device is
a TPU.  Its last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

PALLAS_LABELS = ("pallas", "auto:pallas")
AAQ_MIN_TM = 0.995
# FP: coords allclose at this fraction of the reference's extent.  The
# model computes in bf16 through 48 random-weight blocks, where a change of
# reduction order alone moves coords by a few percent of their extent.
FP_ATOL_FRAC = 0.1
LENGTHS = (100, 160, 200, 250, 470, 1000)
FP_LENGTH = 470                 # the request also served under the FP scheme
FOUR_CHIP_LENGTHS = (400, 500)
BUDGET_MB = 6000                # per device; leaves room for params + refs
SEED = 0                        # parameters and sequences


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def tpu_devices():
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX's first device is "
                           f"{devices[0].platform!r}; this test never runs "
                           f"on another backend")
    return devices


def serve_args(scheme: str, buckets: str, extra=()):
    from repro.launch import serve
    return serve.build_parser().parse_args(
        ["--mode", "ppm", "--ppm-config", "esmfold_ppm", "--scheme", scheme,
         "--kernels", "auto", "--buckets", buckets, "--chunk-size", "auto",
         "--mem-budget-mb", str(BUDGET_MB), "--no-fidelity",
         "--max-tokens-per-batch", "1024", *extra])


def serve(args, cfg, params, seqs, *, mesh=None):
    """Serve ``seqs`` through the serve entry point's client; returns
    (client, results in submit order)."""
    from repro.launch import serve as sv
    from repro.serving import parse_buckets
    client = sv.fold_client(args, cfg, params,
                            parse_buckets(args.buckets, 0, 0), mesh=mesh)
    handles = [client.submit(s) for s in seqs]
    client.drive()
    results = [h.result() for h in handles]
    for r in results:
        check(r.ok, f"request {r.request_id} (len {r.length}) ended "
                    f"{r.status}: {r.reason}")
    check(not client.driver_errors,
          f"driver errors: {list(client.driver_errors)}")
    return client, results


def compile_seconds(client) -> float:
    return sum(e.compile_ms for e in client.core.cost_model.entries.values()
               ) / 1e3


def reference_coords(cfg, params, scheme, seq, bucket, chunk, cache):
    """Coords of ``seq`` from jit(ppm_forward) under the XLA refs, padded
    to the served bucket at batch 1 with the served chunk plan.  Returns
    (coords, compile seconds spent here)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import dispatch
    from repro.models.ppm import ppm_forward
    from repro.serving import pad_to_bucket

    key = (scheme.name, bucket, chunk)
    spent = 0.0
    if key not in cache:
        t0 = time.perf_counter()
        with dispatch.use_backend(dispatch.REF):
            fn = jax.jit(lambda p, a, m: ppm_forward(
                p, a, cfg, scheme, mask=m, chunk_size=chunk or None))
            cache[key] = fn.lower(
                params, jax.ShapeDtypeStruct((1, bucket), jnp.int32),
                jax.ShapeDtypeStruct((1, bucket), jnp.bool_)).compile()
        spent = time.perf_counter() - t0
    aat, mask = pad_to_bucket([seq], bucket)
    out = cache[key](params, jnp.asarray(aat), jnp.asarray(mask))
    return np.asarray(out["coords"][0, :len(seq)]), spent


def tm(a, b) -> float:
    import jax.numpy as jnp

    from repro.models.ppm import tm_score
    return float(tm_score(jnp.asarray(a), jnp.asarray(b)))


def peak_bytes(device):
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def assert_fp_close(what, r, got, want) -> None:
    import numpy as np
    atol = FP_ATOL_FRAC * float(np.max(np.abs(want)))
    err = float(np.max(np.abs(got - want)))
    print(f"# {what} len={r.length} bucket={r.bucket} "
          f"backend={r.kernel_backend} max_abs_diff={err:.4e} atol={atol:.4e} "
          f"tm={tm(got, want):.6f}", flush=True)
    np.testing.assert_allclose(got, want, rtol=0, atol=atol)


def largest_executable(clients):
    best = None
    for c in clients:
        for key, exe in c.core._executables.items():
            ma = exe.memory_analysis()
            size = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                    + ma.temp_size_in_bytes)
            if best is None or size > best[0]:
                best = (size, key, ma)
    return best


def one_chip(cfg, params, seqs) -> None:
    import numpy as np

    from repro.core import make_scheme

    aaq_args = serve_args("lightnobel_aaq", "256,512,1024")
    client, results = serve(aaq_args, cfg, params, seqs)
    scheme = client.core.scheme
    refs: dict = {}
    ref_compile_s = 0.0
    print("length,bucket,chunk,backend,tm_vs_ref,run_ms,compile_s")
    for seq, r in zip(seqs, results):
        check(r.kernel_backend in PALLAS_LABELS,
              f"request {r.request_id} ran under {r.kernel_backend!r}")
        check(np.isfinite(r.coords).all(),
              f"request {r.request_id}: non-finite coords")
        ref, spent = reference_coords(cfg, params, scheme, seq, r.bucket,
                                      r.chunk_size, refs)
        ref_compile_s += spent
        score = tm(r.coords, ref)
        print(f"{r.length},{r.bucket},{r.chunk_size},{r.kernel_backend},"
              f"{score:.6f},{r.run_ms:.1f},{r.compile_ms / 1e3:.1f}",
              flush=True)
        check(score >= AAQ_MIN_TM,
              f"request {r.request_id}: TM {score:.4f} < {AAQ_MIN_TM}")

    # one request under the FP scheme: allclose to its ref forward
    seq = next(s for s in seqs if len(s) == FP_LENGTH)
    fp_client, [fr] = serve(serve_args("baseline_fp16", "512"), cfg, params,
                            [seq])
    check(fr.kernel_backend in PALLAS_LABELS,
          f"fp request ran under {fr.kernel_backend!r}")
    fref, spent = reference_coords(cfg, params, make_scheme("baseline_fp16"),
                                   seq, fr.bucket, fr.chunk_size, refs)
    ref_compile_s += spent
    assert_fp_close("fp", fr, fr.coords, fref)

    served_s = compile_seconds(client) + compile_seconds(fp_client)
    print(f"# compile_s served={served_s:.1f} ref={ref_compile_s:.1f} "
          f"total={served_s + ref_compile_s:.1f}")
    size, key, ma = largest_executable([client, fp_client])
    print(f"# largest executable {key}: {size / 1e9:.3f} GB "
          f"(args {ma.argument_size_in_bytes / 1e9:.3f} GB, "
          f"out {ma.output_size_in_bytes / 1e9:.3f} GB, "
          f"temp {ma.temp_size_in_bytes / 1e9:.3f} GB)")


def four_chips(cfg, params, seqs, devices) -> None:
    import numpy as np

    from repro.serving import make_serving_mesh

    mesh = make_serving_mesh("1x4")
    extra = ("--mesh", "1x4", "--shard-threshold", "512")
    out = {}
    for scheme in ("lightnobel_aaq", "baseline_fp16"):
        args = serve_args(scheme, "512", extra)
        client, res = serve(args, cfg, params, seqs, mesh=mesh)
        for r in res:
            check(r.placement == "mesh:1x4",
                  f"request {r.request_id} placed {r.placement!r}")
            check(r.kernel_backend in PALLAS_LABELS,
                  f"request {r.request_id} ran under {r.kernel_backend!r}")
        out[scheme] = (client, res)
    # before any single-device run: what the sharded phase left on each chip
    print("# sharded phase peak_bytes_in_use per device: " + " ".join(
        f"{d.id}:{peak_bytes(d)}" for d in devices), flush=True)
    for scheme, (sh_client, sharded) in out.items():
        si_client, single = serve(serve_args(scheme, "512"), cfg, params,
                                  seqs)
        for client, label in ((sh_client, "mesh:1x4"), (si_client, "single")):
            size, key, ma = largest_executable([client])
            print(f"# {scheme} {label} executable {key}: temp per device "
                  f"{ma.temp_size_in_bytes / 1e9:.3f} GB, args "
                  f"{ma.argument_size_in_bytes / 1e9:.3f} GB", flush=True)
        print("length,bucket,backend,tm_vs_single,run_ms_sharded,"
              "run_ms_single")
        for a, b in zip(sharded, single):
            check(b.placement == "single", f"single placed {b.placement!r}")
            score = tm(a.coords, b.coords)
            print(f"{a.length},{a.bucket},{a.kernel_backend},{score:.6f},"
                  f"{a.run_ms:.1f},{b.run_ms:.1f}", flush=True)
            if scheme == "baseline_fp16":
                assert_fp_close("fp sharded-vs-single", a, a.coords, b.coords)
            else:
                check(score >= AAQ_MIN_TM, f"sharded TM {score:.4f}")
    print("# peak_bytes_in_use per device: " + " ".join(
        f"{d.id}:{peak_bytes(d)}" for d in devices))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh-sharded (1x4) phase")
    args = ap.parse_args(argv)
    try:
        devices = tpu_devices()
        if args.four_chips:
            check(len(devices) >= 4, f"--four-chips needs 4 devices, "
                                     f"found {len(devices)}")
            devices = devices[:4]
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "src"))
        import numpy as np

        from repro.kernels import dispatch
        from repro.launch.compile_cache import place_compile_cache
        from repro.launch.serve import ppm_model

        print(f"# compile cache: {place_compile_cache()}")
        dispatch.set_backend(dispatch.AUTO)
        t0 = time.perf_counter()
        cfg, params = ppm_model("esmfold_ppm", SEED)
        rng = np.random.default_rng(SEED)
        lengths = FOUR_CHIP_LENGTHS if args.four_chips else LENGTHS
        seqs = [rng.integers(0, 20, n).astype(np.int32) for n in lengths]
        if args.four_chips:
            four_chips(cfg, params, seqs, devices)
        else:
            one_chip(cfg, params, seqs)
            print(f"# peak_bytes_in_use={peak_bytes(devices[0])}")
        print(f"# wall_s={time.perf_counter() - t0:.1f}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
