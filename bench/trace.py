"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``reduce_profile(dir)`` reads the ``.xplane.pb`` the profiler wrote under
``dir`` and keeps, for the run's chip (``/device:TPU:0``):

* the traced window: the host annotation ``bench.window`` the harness puts
  around the window and its drain (the span of the device's ops where the
  annotation is missing);
* ``busy_s``: the union of the intervals in which an op of the device's
  ``XLA Ops`` line ran, inside the window; the idle share is 1 - busy over
  the window;
* ``kernel_s``: the device seconds of each kernel of ``KERNEL_NAMES``: a
  Pallas call's op carries the name of its jitted wrapper
  (``%flash_mha_pallas.26 = ... custom-call(...)``), and
  ``kernel_calls`` keeps each call's instruction, whose types give the
  shapes ``bench/ops/kernels.py`` counts the work at;
* the ops that took most time, each kernel over all its call sites and
  every other op by HLO instruction, summed over the scan's iterations (a
  loop's own interval holds its body's ops, so loops count as busy and
  never as an op), and the longest idle gaps, each named by
  the host annotation (``serve.dispatch/<bucket>``,
  ``serve.retire/<bucket>``) that overlaps it most.

``reduce`` does the arithmetic on plain (instruction, start, end) tuples,
so tests can hold it to hand-made traces.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

#: kernel -> the names its device ops carry
KERNEL_NAMES = {
    "flash_mha_pallas": ("flash_mha_pallas", "_flash_kernel"),
    "aaq_quantize_pallas": ("aaq_quantize_pallas", "_quant_kernel"),
    "aaq_matmul_pallas": ("aaq_matmul_pallas", "_qmm_kernel"),
}
DEVICE_PLANE = "/device:TPU:0"
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
HOST_PREFIXES = ("serve.dispatch/", "serve.retire/")
#: ops whose interval holds other ops' (a scan's loop): busy, never an op
CONTAINERS = ("while", "call", "conditional")
TOP = 10


@dataclass
class Reduction:
    window_s: float
    busy_s: float
    kernel_s: dict[str, float] = field(default_factory=dict)
    # kernel -> {instruction text: calls}, the shapes each call ran with
    kernel_calls: dict[str, dict[str, int]] = field(default_factory=dict)
    ops: list = field(default_factory=list)         # [name, seconds]
    idle_gaps: list = field(default_factory=list)   # [name, seconds]

    def breakdown(self) -> dict:
        return {"device_ops": self.ops[:TOP],
                "idle_gaps": self.idle_gaps[:TOP]}


def kernel_of(op: str) -> str | None:
    """The kernel an op is an instance of, by the op's own name
    (``flash_mha_pallas.26``), never by its operands'."""
    base = op.lstrip("%").split(".")[0]
    for kernel, names in KERNEL_NAMES.items():
        if base in names:
            return kernel
    return None


def parse_op(text: str) -> tuple[str, str, str]:
    """(op name, short label, opcode) of one HLO instruction as the trace
    names it: ``%fusion.7 = bf16[2,128]{1,0:T(8,128)} fusion(...), ...``
    -> (``fusion.7``, ``fusion.7 bf16[2,128] fusion``, ``fusion``)."""
    lhs, _, rhs = text.partition(" = ")
    op = lhs.strip().lstrip("%")
    if not rhs:
        return op, op, op
    end, depth = 0, 0                     # the result type: a shape, or a
    for end, ch in enumerate(rhs):        # parenthesized tuple of shapes
        depth += (ch == "(") - (ch == ")")
        if ch == " " and depth == 0:
            break
    shape = re.sub(r"\{[^{}]*\}", "", rhs[:end])
    opcode = rhs[end + 1:].split("(", 1)[0]
    if shape.startswith("("):
        shape = "(tuple)"
    return op, f"{op} {shape} {opcode}".strip(), opcode


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Disjoint, sorted cover of ``intervals`` (start, end)."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _overlap(a, b) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def reduce(ops: list[tuple[str, float, float]],
           host: list[tuple[str, float, float]],
           window: tuple[float, float] | None = None) -> Reduction:
    """``ops``: device ops as (HLO instruction text, start_s, end_s);
    ``host``: host annotations as (name, start_s, end_s); ``window``: the
    traced window (default: the span of the ops)."""
    if not ops:
        return Reduction(window_s=0.0, busy_s=0.0)
    if window is None:
        window = (min(o[1] for o in ops), max(o[2] for o in ops))
    lo, hi = window
    inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
              if e > lo and s < hi]
    busy = union([(s, e) for _, s, e in inside])
    kernel_s: dict[str, float] = {}
    kernel_calls: dict[str, dict[str, int]] = {}
    by_name: dict[str, float] = {}
    for text, s, e in inside:
        op, label, opcode = parse_op(text)
        if opcode in CONTAINERS:
            continue
        k = kernel_of(op)
        if k is not None:
            kernel_s[k] = kernel_s.get(k, 0.0) + (e - s)
            calls = kernel_calls.setdefault(k, {})
            calls[text] = calls.get(text, 0) + 1
        by_name[k or label] = by_name.get(k or label, 0.0) + (e - s)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]
    marks = [h for h in host if h[0].startswith(HOST_PREFIXES)]
    named = []
    for g in gaps:
        best = max(marks, key=lambda h: _overlap(g, h[1:]), default=None)
        name = (best[0] if best is not None and _overlap(g, best[1:]) > 0
                else "host:other")
        named.append([name, g[1] - g[0]])
    named.sort(key=lambda x: -x[1])
    top_ops = sorted(([n, s] for n, s in by_name.items()),
                     key=lambda x: -x[1])
    return Reduction(window_s=hi - lo,
                     busy_s=sum(e - s for s, e in busy),
                     kernel_s=kernel_s, kernel_calls=kernel_calls,
                     ops=top_ops, idle_gaps=named)


def read_xplane(path: str):
    """(device ops, host annotations, window) of one ``.xplane.pb``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    ops, host, window = [], [], None
    for plane in data.planes:
        if plane.name == DEVICE_PLANE:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    ops.append((e.name, s, s + e.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        s = e.start_ns * 1e-9
                        window = (s, s + e.duration_ns * 1e-9)
                    elif e.name.startswith(HOST_PREFIXES):
                        s = e.start_ns * 1e-9
                        host.append((e.name, s, s + e.duration_ns * 1e-9))
    return ops, host, window


def reduce_profile(trace_dir) -> Reduction:
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(*read_xplane(files[-1]))
