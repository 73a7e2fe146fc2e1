from repro.kernels.aaq_matmul.ops import aaq_linear
