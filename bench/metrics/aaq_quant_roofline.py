"""Kernels: ``aaq_quantize_pallas``'s roofline share in % (``bench/ops/kernels.py``)."""

from bench.ops.kernels import roofline_share


def read(run):
    return roofline_share(run, "aaq_quantize_pallas")
