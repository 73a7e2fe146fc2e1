"""Kernel dispatch: one routing point between the Pallas kernels and the
XLA reference implementations.

Every attention and quantized-matmul call site in the model zoo (PPM trunk
row/column attention, chunked triangular attention, the structure module,
the LM/encdec/MoE families, and ``AAQScheme.linear``) goes through
``attention`` / ``quantized_linear`` here instead of importing a concrete
implementation.  Backend selection, per call:

  1. an explicit ``backend=`` argument (tests, microbenches),
  2. the process-wide mode set by ``set_backend`` — this is what the
     ``--kernels {pallas,ref,auto}`` launcher flag drives,
  3. in ``auto`` mode, backend capability plus shape heuristics: Pallas
     only on a real TPU, and only for shapes big enough that the fused
     kernel beats XLA's fusion (tiny decode/test shapes stay on the ref).

An explicit ``pallas`` request off-TPU runs the kernels in interpret mode
(``pl.pallas_call(interpret=True)``), so CPU CI executes the real kernel
bodies — same grid, same block program — without TPU hardware.

Under a mesh (the sharded serving tier traces inside ``act_rules`` that
carry ``KERNEL_MESH``) a Pallas call is wrapped in ``jax.shard_map``:
GSPMD cannot partition a Mosaic kernel, so each device runs the kernel on
its shard — attention split over heads, the AAQ linear over tokens — or,
where the shape does not divide the mesh, on a replicated copy.

Counters: each routed call bumps ``counters[...]`` at *trace* time.  Inside
``jit``/``scan`` that is once per compilation (or once per scanned body
trace), not once per executed step; the parity suite uses the counters to
prove which kernel path a compiled forward actually contains.
"""
from __future__ import annotations

import contextlib
import math
import os

import jax
from jax.sharding import PartitionSpec as P

from repro.core.qmatmul import qmatmul_fused_ref
from repro.kernels.aaq_matmul.ops import aaq_linear
from repro.kernels.flash_attention.flash_attention import flash_mha_pallas
from repro.kernels.flash_attention.ref import mha_chunked
from repro.parallel.sharding import KERNEL_MESH, MODEL, rule_value

REF = "ref"
PALLAS = "pallas"
AUTO = "auto"
BACKENDS = (REF, PALLAS, AUTO)

# auto-mode shape floors: below these the kernel-launch bookkeeping beats
# any fusion win, so auto stays on the XLA ref even on TPU.  These are the
# STATIC fallbacks (unprofiled estimates); calibrated crossover points from
# a measured cost table override them via ``set_calibrated_floors`` (the
# ``--cost-table`` serve flow) or the REPRO_MIN_FLASH_SEQ /
# REPRO_MIN_QMM_TOKENS env vars, and auto labels then say so
# (``auto:calibrated:...`` vs the plain static ``auto:...``).
MIN_FLASH_SEQ = 128          # min(Sq, Skv) for the flash path
MIN_QMM_TOKENS = 64          # flattened token count for the AAQ matmul

ENV_FLASH_SEQ = "REPRO_MIN_FLASH_SEQ"
ENV_QMM_TOKENS = "REPRO_MIN_QMM_TOKENS"

_CALIBRATED_FLOORS: dict[str, int] = {}


def set_calibrated_floors(*, flash_seq: int | None = None,
                          qmm_tokens: int | None = None) -> None:
    """Install measured crossover points (from a calibrated cost table) as
    the auto-mode floors process-wide.  ``None`` leaves that floor static."""
    _CALIBRATED_FLOORS.clear()
    if flash_seq is not None:
        _CALIBRATED_FLOORS["flash_seq"] = int(flash_seq)
    if qmm_tokens is not None:
        _CALIBRATED_FLOORS["qmm_tokens"] = int(qmm_tokens)


def clear_calibrated_floors() -> None:
    _CALIBRATED_FLOORS.clear()


def _env_floor(name: str) -> int | None:
    v = os.environ.get(name)
    if v is None or v == "":
        return None
    try:
        return int(v)
    except ValueError as err:
        raise ValueError(f"{name}={v!r} is not an int") from err


def effective_floors() -> tuple[int, int, str]:
    """The auto-mode floors in force right now: ``(flash_seq, qmm_tokens,
    source)`` with source ``"calibrated"`` when either floor comes from a
    cost table or env override, ``"static"`` otherwise.  Env vars are read
    at call time so tests and one-off runs can override without imports
    racing."""
    flash = _env_floor(ENV_FLASH_SEQ)
    qmm = _env_floor(ENV_QMM_TOKENS)
    if flash is None:
        flash = _CALIBRATED_FLOORS.get("flash_seq")
    if qmm is None:
        qmm = _CALIBRATED_FLOORS.get("qmm_tokens")
    source = "calibrated" if (flash is not None or qmm is not None) \
        else "static"
    return (flash if flash is not None else MIN_FLASH_SEQ,
            qmm if qmm is not None else MIN_QMM_TOKENS,
            source)


def floors_source() -> str:
    return effective_floors()[2]

# interpret-mode block override: the interpreter executes the grid serially
# with a large fixed per-step overhead, so correctness-path runs want the
# fewest, fattest blocks (VMEM limits don't apply off-chip); compiled TPU
# runs keep the MXU-aligned 128/256 defaults
INTERP_BLOCK_SEQ = 1024      # flash block_q/block_k cap
INTERP_BLOCK_T = 4096        # aaq quant/matmul token-block cap
INTERP_BLOCK_D = 1024        # aaq matmul output-block cap

_MODE = AUTO

counters: dict[str, int] = {
    "attention.pallas": 0,
    "attention.ref": 0,
    "qmatmul.pallas": 0,
    "qmatmul.ref": 0,
}


def reset_counters() -> None:
    for k in counters:
        counters[k] = 0


def _check(mode: str) -> str:
    if mode not in BACKENDS:
        raise ValueError(f"unknown kernel backend {mode!r}; pick one of {BACKENDS}")
    return mode


def set_backend(mode: str) -> None:
    """Set the process-wide backend mode (the ``--kernels`` flag)."""
    global _MODE
    _MODE = _check(mode)


def get_backend() -> str:
    return _MODE


@contextlib.contextmanager
def use_backend(mode: str):
    """Scoped ``set_backend`` — traces (incl. ``jit.lower``) inside the
    ``with`` block route through ``mode``."""
    global _MODE
    prev = _MODE
    _MODE = _check(mode)
    try:
        yield
    finally:
        _MODE = prev


def interpret_mode() -> bool:
    """Pallas kernels must run interpreted off-TPU (CPU CI, dry runs)."""
    return jax.default_backend() != "tpu"


def _resolve(backend: str | None, auto_wants_pallas: bool) -> str:
    mode = _check(backend) if backend is not None else _MODE
    if mode != AUTO:
        return mode
    if jax.default_backend() != "tpu":
        return REF
    return PALLAS if auto_wants_pallas else REF


def resolve_attention(sq: int, skv: int, *, backend: str | None = None) -> str:
    return _resolve(backend, min(sq, skv) >= effective_floors()[0])


def resolve_matmul(n_tokens: int, *, backend: str | None = None) -> str:
    return _resolve(backend, n_tokens >= effective_floors()[1])


def attention_is_pallas(sq: int, skv: int, *, backend: str | None = None) -> bool:
    """Will ``attention`` take the Pallas path for this shape?  Call sites
    with a kernel-friendly rewrite (tri-attn's row flattening) use this to
    pick the dataflow before building operands."""
    return resolve_attention(sq, skv, backend=backend) == PALLAS


def describe(backend: str | None = None, *, seq: int | None = None,
             qmm_tokens: int | None = None) -> str:
    """Stable human/report label for the backend a mode resolves to.

    For ``auto`` the label is capability-only unless shape hints are given,
    in which case BOTH per-op floors are folded in: ``seq`` (a
    representative attention length, e.g. the serving bucket) resolves the
    flash path against MIN_FLASH_SEQ and ``qmm_tokens`` (the flattened
    token count the quantized linears see; defaults to ``seq**2`` — one
    pair-dataflow row set at batch 1) resolves the AAQ matmul against
    MIN_QMM_TOKENS.  When the two resolutions agree the label stays
    ``auto:<backend>``; when they split it reports both —
    ``auto:attn=<a>,qmm=<q>`` — instead of letting the attention floor
    speak for matmuls that actually run the other path.  With only
    ``qmm_tokens`` given there is no attention shape to resolve against,
    so the attention half is honestly unknown — ``auto:attn=?;qmm=<q>`` —
    rather than a capability-only guess claiming pallas for attention.

    When calibrated floors are in force (cost table / env override) the
    auto prefix becomes ``auto:calibrated:`` so reports show whether the
    resolution was priced on measured crossovers or the static estimates
    (plain ``auto:`` is the static form).
    """
    mode = _check(backend) if backend is not None else _MODE
    interp = interpret_mode()

    def tag(inner: str) -> str:
        return "pallas-interpret" if inner == PALLAS and interp else inner

    if mode == AUTO:
        prefix = "auto:calibrated" if floors_source() == "calibrated" \
            else "auto"
        if seq is None and qmm_tokens is None:
            return f"{prefix}:{tag(_resolve(AUTO, True))}"
        if qmm_tokens is None:
            qmm_tokens = seq * seq
        qmm = resolve_matmul(qmm_tokens, backend=AUTO)
        if seq is None:
            return f"{prefix}:attn=?;qmm={tag(qmm)}"
        attn = resolve_attention(seq, seq, backend=AUTO)
        if attn == qmm:
            return f"{prefix}:{tag(attn)}"
        return f"{prefix}:attn={tag(attn)};qmm={tag(qmm)}"
    return tag(mode)


# --------------------------------------------------------------------------
# routed ops
# --------------------------------------------------------------------------
def _per_device(fn, args, specs, out_spec):
    """``fn(*args)``, or under a kernel mesh, ``fn`` on each device's shard
    (``specs`` name the ``MODEL``-split axis of each arg)."""
    mesh = rule_value(KERNEL_MESH)
    if mesh is None:
        return fn(*args)
    return jax.shard_map(fn, mesh=mesh, in_specs=tuple(specs),
                         out_specs=out_spec, check_vma=False)(*args)


def _split(*sizes):
    """``MODEL`` when every size divides the kernel mesh's model axis, else
    None (each device runs the whole call)."""
    mesh = rule_value(KERNEL_MESH)
    if mesh is None:
        return None
    return MODEL if all(s % mesh.shape[MODEL] == 0 for s in sizes) else None


def attention(q, k, v, *, bias=None, causal=False, window=None,
              kv_valid_len=None, softmax_scale=None, q_chunk=512,
              block_q=128, block_k=128, backend=None):
    """Token-wise MHA: q (B,Sq,Hq,D); k,v (B,Skv,Hkv,D); bias (Bb,Hq,Sq,Skv)
    with block batch-broadcast (bias row t covers B//Bb consecutive q rows).

    Pallas path: the fused flash kernel (interpret mode off-TPU).  Ref
    path: ``mha_chunked`` — bitwise the pre-dispatch model numerics.
    """
    be = resolve_attention(q.shape[1], k.shape[1], backend=backend)
    if be == PALLAS:
        counters["attention.pallas"] += 1
        interp = interpret_mode()
        if interp:
            block_q = max(block_q, min(q.shape[1], INTERP_BLOCK_SEQ))
            block_k = max(block_k, min(k.shape[1], INTERP_BLOCK_SEQ))
        has_bias, has_kvl = bias is not None, kv_valid_len is not None

        def call(q, k, v, *rest):
            return flash_mha_pallas(
                q, k, v, rest[0] if has_bias else None,
                rest[-1] if has_kvl else None, causal=causal, window=window,
                softmax_scale=softmax_scale, block_q=block_q,
                block_k=block_k, interpret=interp)

        h = _split(q.shape[2], k.shape[2])              # heads are independent
        args, specs = [q, k, v], [P(None, None, h, None)] * 3
        if has_bias:
            args.append(bias)
            specs.append(P(None, h, None, None))
        if has_kvl:
            args.append(kv_valid_len)
            specs.append(P())
        return _per_device(call, args, specs, P(None, None, h, None))
    counters["attention.ref"] += 1
    return mha_chunked(q, k, v, bias=bias, causal=causal, window=window,
                       kv_valid_len=kv_valid_len, softmax_scale=softmax_scale,
                       q_chunk=q_chunk)


def quantized_linear(x, w, *, bits: int, k_outliers: int, bias=None,
                     backend=None):
    """AAQ linear  y = dequant-free-matmul(quantize(x), w) (+ bias).

    Pallas path: the packed aaq_quant + aaq_matmul kernels — the bucketed
    executables compute on INT4/INT8 inliers with the deferred per-token
    scale, never materializing a dequantized activation.  Ref path:
    ``qmatmul_fused_ref`` (same integer-path math, XLA-fused).
    """
    n_tokens = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
    be = resolve_matmul(n_tokens, backend=backend)
    if be == PALLAS:
        counters["qmatmul.pallas"] += 1
        interp = interpret_mode()
        block_t = min(max(n_tokens, 1), INTERP_BLOCK_T) if interp else 256
        block_d = min(w.shape[-1], INTERP_BLOCK_D) if interp else 256
        x2 = x.reshape(n_tokens, x.shape[-1])
        t = _split(n_tokens)                            # tokens are independent
        y = _per_device(
            lambda a, b: aaq_linear(a, b, bits=bits, k_outliers=k_outliers,
                                    use_kernel=True, interpret=interp,
                                    block_t=block_t, block_d=block_d),
            (x2, w), (P(t, None), P()), P(t, None))
        y = y.reshape(*x.shape[:-1], w.shape[-1])
    else:
        counters["qmatmul.ref"] += 1
        y = qmatmul_fused_ref(x, w, bits, k_outliers)
    return y if bias is None else y + bias
