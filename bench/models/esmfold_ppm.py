"""ESMFold's folding trunk, the paper's PPM: a request is one sequence of
residue indices (int32)."""
from __future__ import annotations

from bench.ops.ppm import fold_flops
from bench.reference.ppm import Reference
from bench.weights import make_params

__all__ = ["TINY", "Reference", "fold_flops", "init_program_params",
           "make_client", "make_params", "program_config"]

TINY = "bench/testdata/tiny_aaq.json"


def program_config(cfg: dict):
    from repro.models.ppm import PPMConfig
    keys = PPMConfig.__dataclass_fields__
    return PPMConfig(**{k: cfg[k] for k in keys})


def make_client(cfg: dict, traffic: dict, params):
    from bench.run import program_scheme, serve_args
    from repro.launch.serve import fold_client
    args = serve_args(cfg, traffic)
    args.scheme = program_scheme(cfg)
    return fold_client(args, program_config(cfg), params,
                       tuple(traffic["buckets"]))


def init_program_params(key, cfg: dict):
    from repro.models.ppm import init_ppm
    return init_ppm(key, program_config(cfg))
