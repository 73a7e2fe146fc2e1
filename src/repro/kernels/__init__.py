"""Pallas TPU kernels for the perf-critical hot spots (each: kernel +
ref.py pure-jnp oracle; compiled for the chip, interpreted on CPU tests):

  aaq_quant       fused token-wise AAQ runtime quantization (VVPU analogue)
  aaq_matmul      dequantization-free INT4/INT8 matmul, deferred per-token
                  scale + rank-k outlier correction (RMPU analogue)
  flash_attention token-wise MHA with pair bias / causal / SWA / GQA /
                  kv_valid_len (the paper's §5.4 dataflow, generalized)

``dispatch`` is the routing layer every model call site goes through: it
selects Pallas vs ref per call from the ``--kernels {pallas,ref,auto}``
mode, backend capability, and shape heuristics (interpret mode off-TPU).
Kernel entry points take ``interpret`` with no default: only ``dispatch``
decides it.
"""
from repro.kernels.aaq_matmul import aaq_linear
from repro.kernels.aaq_quant import aaq_quantize
from repro.kernels.flash_attention import flash_mha_pallas
from repro.kernels import dispatch
