"""The work of each traced kernel call, from the shapes it ran with.

A call is (operations, least bytes): the operations the algorithm needs at
the call's shapes and the bytes it must read and write at least once.  The
least time of a call on a chip is the larger of operations over the peak
rate and bytes over the memory bandwidth; a kernel's roofline share is the
least time of its calls over the device time they took.

The shapes come from the trace itself: a Pallas call's op is named after
its jitted wrapper and carries its result and operand types
(``%flash_mha_pallas.26 = bf16[2,16,128,64]{...} custom-call(bf16[...]
%q, ...)``).  So the share counts the calls that ran, after XLA has merged
duplicates (two linears that quantize one input with one policy make one
``aaq_quantize_pallas`` call) and whatever the row chunking was.

* ``flash_mha_pallas``: 4 B H S T D FLOPs (q.k and p.v); it reads q, k, v,
  the bias and key lengths, and writes the output, once.
* ``aaq_quantize_pallas``: (k + 3) passes over a (T, H) block (top-k
  rounds, the scale's max, the rounding); it reads x and writes the packed
  inliers, the scales and the (T, k) outlier values and indices.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass


@dataclass(frozen=True)
class Call:
    ops: float          # operations (FLOPs for products)
    bytes: float        # least bytes read and written

    def least_s(self, peak_flops: float, hbm_bytes_s: float) -> float:
        return max(self.ops / peak_flops, self.bytes / hbm_bytes_s)

    def bound(self, peak_flops: float, hbm_bytes_s: float) -> str:
        return ("compute" if self.ops / peak_flops >= self.bytes / hbm_bytes_s
                else "memory")


_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2,
                "s32": 4, "u32": 4, "f32": 4}
_SHAPE = re.compile(r"\b(pred|s8|u8|bf16|f16|s32|u32|f32)\[([\d,]*)\]")


def hlo_shapes(text: str):
    """(results, operands) of a custom call as the trace names it, each a
    list of (dtype, dims): ``%k.3 = bf16[2,8]{...} custom-call(f32[2,8]{...}
    %x), ...``.  Only the call's own operand list is read."""
    rhs = text.partition(" = ")[2]
    head, _, rest = rhs.partition("custom-call(")
    depth, end = 1, len(rest)
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            end = i
            break

    def shapes(part):
        return [(dt, [int(d) for d in dims.split(",") if d])
                for dt, dims in _SHAPE.findall(part)]

    return shapes(head), shapes(rest[:end])


def _nbytes(shapes) -> int:
    return sum(_DTYPE_BYTES[dt] * math.prod(dims) for dt, dims in shapes)


def flash_call(text: str) -> Call:
    """``flash_mha_pallas`` at its traced shapes: q (B, H, S, D) and k
    (B, Hkv, T, D) head-major, then v, an optional bias and key lengths;
    it reads each operand and writes its output once."""
    results, args = hlo_shapes(text)
    b, h, s, d = args[0][1]
    t = args[1][1][2]
    return Call(4 * b * h * s * t * d, _nbytes(results) + _nbytes(args))


def quantize_call(text: str) -> Call:
    """``aaq_quantize_pallas`` at its traced shapes: x (T, H) in; packed
    inliers, scales and the (T, k) outlier values and indices out."""
    results, args = hlo_shapes(text)
    tokens, h = args[0][1]
    k = results[2][1][1]
    return Call(tokens * h * (k + 3), _nbytes(results) + _nbytes(args))


#: kernel -> its call's work from the trace's instruction text
KERNELS = {"flash_mha_pallas": flash_call,
           "aaq_quantize_pallas": quantize_call}


def roofline_share(run, kernel: str, work=None) -> float | None:
    """``kernel``'s roofline share in a traced run, in %: the least time
    of its traced calls, each at the shapes it ran with, over the device
    time they took.  None where the trace shows no call of it.  ``work``
    maps a call's instruction text to its ``Call``; by default the
    kernel's entry in ``KERNELS``, so a later kernel's metric passes its
    own from a file of its own."""
    if run.trace is None or not run.peak:
        return None
    spent = run.trace.kernel_s.get(kernel, 0.0)
    calls = run.trace.kernel_calls.get(kernel, {})
    if spent <= 0.0 or not calls:
        return None
    work = work or KERNELS[kernel]
    least = sum(n * work(text).least_s(run.peak["bf16_flops_s"],
                                       run.peak["hbm_bytes_s"])
                for text, n in calls.items())
    return 100.0 * least / spent
