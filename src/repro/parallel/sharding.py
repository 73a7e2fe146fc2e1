"""Logical-axis sharding rules (MaxText-style) for every model in the zoo.

The distribution layer never hardcodes mesh axes into models: parameters and
step inputs get PartitionSpecs from *rules* keyed on parameter-path regexes
and logical input axes.  Changing the mesh (16x16 single-pod, 2x16x16
multi-pod, or a hypothetical 64x64) is a rules change, not a model change.

Placement summary (DESIGN.md §5):
  * DP over ("pod","data") for batch; Megatron TP over "model"
    (column-parallel QKV/up/gate, row-parallel O/down => one psum per block);
  * EP over "model" when n_experts divides |model| (deepseek 64/16), else TP
    inside the expert FFN (mixtral 8 experts -> d_ff sharding);
  * KV caches: batch over data, kv_heads over model when divisible else
    head_dim over model;
  * PPM pair tensor: row i over "data", column j over "model".

Every rule is guarded by divisibility — a dim that does not divide the mesh
axis is replicated rather than producing a GSPMD error.
"""
from __future__ import annotations

import re
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeSpec

DATA = "data"            # logical data axis (maps to ("pod","data") multi-pod)
MODEL = "model"
KERNEL_MESH = "kernel_mesh"   # act-rules entry: the mesh Pallas calls run on


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def data_axes(mesh: Mesh):
    """The composite data-parallel axis for this mesh."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _maybe(mesh: Mesh, dim: int, axis):
    """axis if dim divides its size, else None (replicate)."""
    return axis if dim % _axis_size(mesh, axis) == 0 and dim > 0 else None


# --------------------------------------------------------------------------
# parameter rules
# --------------------------------------------------------------------------
_COL = r"(\.q|\.k|\.v|\.up|\.gate|\.in_x|\.in_gate|\.kv_down|\.k_up|\.v_up|\.in_proj|\.qkv|\.a_proj|\.a_gate|\.b_proj|\.b_gate|\.left|\.right|\.coord|\.bias|\.pair_bias)\.w$"
_ROW = r"(\.o|\.down|\.out|\.out_proj|\.out_gate)\.w$"


FSDP_THRESHOLD = 4 * 1024 * 1024   # elements; above this, 2-axis sharding


def param_spec(path: str, shape: tuple[int, ...], mesh: Mesh,
               cfg: ArchConfig | None = None) -> P:
    """PartitionSpec for one parameter, by path regex.

    Big weights (> FSDP_THRESHOLD elements) additionally shard their second
    dim over the data axis (2-D weight sharding / FSDP): without it a 140B
    MoE's params+optimizer (10 bytes/param) cannot fit 16 GB/chip at TP=16.
    GSPMD inserts the per-use all-gathers; the collective roofline term
    carries the cost and §Perf iterates on it.
    """
    mdl = MODEL
    import math as _m
    big = _m.prod(shape) >= FSDP_THRESHOLD if shape else False
    dp = data_axes(mesh)
    fs = dp if big else None

    def fsd(dim):   # fsdp axis, divisibility-guarded
        return _maybe(mesh, dim, fs) if fs else None

    # --- MoE expert banks: (E, din, dout) --------------------------------
    if re.search(r"experts\..*\.w$", path) and len(shape) == 3:
        e, din, dout = shape
        if e % _axis_size(mesh, mdl) == 0:
            return P(mdl, fsd(din), None)              # EP + fsdp
        if re.search(r"\.down\.w$", path):
            return P(None, _maybe(mesh, din, mdl), fsd(dout))
        return P(None, fsd(din), _maybe(mesh, dout, mdl))  # TP inside expert
    if re.search(r"router\.w$", path):
        return P(None, None)
    # --- embeddings -------------------------------------------------------
    if re.search(r"embed\.e$", path):
        return P(_maybe(mesh, shape[0], mdl), fsd(shape[1]))   # vocab-sharded
    if re.search(r"(relpos|pos_dec)\.e$", path):
        return P(None, None)
    if re.search(r"lm_head\.w$", path):
        return P(fsd(shape[0]), _maybe(mesh, shape[-1], mdl))
    # --- column/row parallel linears ---------------------------------------
    if re.search(_COL, path) and len(shape) == 2:
        return P(fsd(shape[0]), _maybe(mesh, shape[1], mdl))
    if re.search(_ROW, path) and len(shape) == 2:
        return P(_maybe(mesh, shape[0], mdl), fsd(shape[1]))
    # --- conv / per-channel vectors ----------------------------------------
    if re.search(r"conv_w$", path) and len(shape) == 2:
        return P(None, _maybe(mesh, shape[1], mdl))
    if re.search(r"(conv_b|lam)$", path) and len(shape) == 1:
        return P(_maybe(mesh, shape[0], mdl))
    if len(shape) == 2 and big:
        return P(fsd(shape[0]), _maybe(mesh, shape[1], mdl))
    # everything else (norms, biases, scalars): replicated
    return P(*([None] * len(shape)))


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return ".".join(parts)


def param_shardings(param_tree, mesh: Mesh, cfg: ArchConfig | None = None):
    """NamedSharding pytree matching ``param_tree`` (arrays or SDS).

    Scan-stacked block params ('blocks.*' / 'trunk.*' with no integer index)
    carry a leading layer axis; the rule applies to the trailing dims and the
    layer axis is never sharded."""
    def one(path, leaf):
        pstr = _path_str(path)
        segs = pstr.split(".")
        stacked = (segs[0] in ("blocks", "trunk", "periods")
                   and len(segs) > 1 and not segs[1].isdigit())
        if stacked and len(leaf.shape) > 1:
            spec = param_spec(pstr, leaf.shape[1:], mesh, cfg)
            spec = P(None, *spec)
        else:
            spec = param_spec(pstr, leaf.shape, mesh, cfg)
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(one, param_tree)


# --------------------------------------------------------------------------
# step-input rules
# --------------------------------------------------------------------------
def batch_specs(cfg: ArchConfig, shape: ShapeSpec, mesh: Mesh,
                quantized_kv: bool = False) -> Any:
    """PartitionSpecs for the input_specs pytree of this cell."""
    dp = data_axes(mesh)
    b = shape.global_batch
    dp_ok = dp if b % _axis_size(mesh, dp) == 0 else _maybe(mesh, b, "data")

    def tok(s=None):
        return P(dp_ok, None)

    if shape.step == "train":
        batch = {"tokens": tok(), "labels": tok()}
        if cfg.kind == "vlm":
            batch["image_embeds"] = P(dp_ok, None, None)
        if cfg.kind == "encdec":
            batch["audio_frames"] = P(dp_ok, None, None)
        return {"batch": batch}
    if shape.step == "prefill":
        batch = {"tokens": tok()}
        if cfg.kind == "vlm":
            batch["image_embeds"] = P(dp_ok, None, None)
        if cfg.kind == "encdec":
            batch["audio_frames"] = P(dp_ok, None, None)
        return {"batch": batch}
    if shape.step == "decode":
        return {"batch": {"tokens": tok()},
                "cache": cache_specs(cfg, shape, mesh,
                                     quantized_kv=quantized_kv)}
    raise ValueError(shape.step)


def cache_specs(cfg: ArchConfig, shape: ShapeSpec, mesh: Mesh,
                quantized_kv: bool = False):
    """PartitionSpecs for the decode cache pytree (leading layer axis)."""
    dp = data_axes(mesh)
    b = shape.global_batch
    bd = dp if b % _axis_size(mesh, dp) == 0 else None
    mdl_sz = _axis_size(mesh, MODEL)

    def kv_spec(n_kv: int, hd: int, seq_shardable: bool):
        if n_kv % mdl_sz == 0:
            return P(None, bd, None, MODEL, None)
        if hd % mdl_sz == 0:
            return P(None, bd, None, None, MODEL)
        if seq_shardable:
            return P(None, bd, MODEL, None, None)
        return P(None, bd, None, None, None)

    if cfg.kind in ("dense", "vlm") or (cfg.kind == "moe" and not cfg.mla):
        spec = kv_spec(cfg.n_kv_heads, cfg.hd, True)
        out = {"k": spec, "v": spec, "pos": P()}
        if quantized_kv:
            sspec = P(*spec[:-1], None)     # scales: no head-dim sharding
            out["k_scale"] = sspec
            out["v_scale"] = sspec
        return out
    if cfg.kind == "moe" and cfg.mla:
        r = cfg.mla.kv_lora_rank
        return {"latent": P(None, bd, None, _maybe(mesh, r, MODEL)),
                "k_rope": P(None, bd, None, None),
                "pos": P()}
    if cfg.kind == "ssm":
        d_inner = cfg.ssm.expand * cfg.d_model
        nh = d_inner // cfg.ssm.head_dim
        conv_dim = d_inner + 2 * cfg.ssm.d_state
        return {"state": P(None, bd, _maybe(mesh, nh, MODEL), None, None),
                "conv": P(None, bd, None, _maybe(mesh, conv_dim, MODEL)),
                "pos": P()}
    if cfg.kind == "hybrid":
        from repro.models.hybrid import _n_periods_tail
        w = cfg.hybrid.lru_width or cfg.d_model
        rec = {"state": P(None, bd, _maybe(mesh, w, MODEL)),
               "conv": P(None, bd, None, _maybe(mesh, w, MODEL))}
        attn = {"k": P(None, bd, None, None, _maybe(mesh, cfg.hd, MODEL)),
                "v": P(None, bd, None, None, _maybe(mesh, cfg.hd, MODEL))}
        period = {f"b{j}": (attn if j == cfg.hybrid.attn_every - 1 else rec)
                  for j in range(cfg.hybrid.attn_every)}
        _, tail = _n_periods_tail(cfg)
        tail_spec = [{"state": P(bd, _maybe(mesh, w, MODEL)),
                      "conv": P(bd, None, _maybe(mesh, w, MODEL))}
                     for _ in range(tail)]
        return {"periods": period, "tail": tail_spec, "pos": P()}
    if cfg.kind == "encdec":
        return {"k": kv_spec(cfg.n_kv_heads, cfg.hd, True),
                "v": kv_spec(cfg.n_kv_heads, cfg.hd, True),
                "enc_out": P(bd, None, _maybe(mesh, cfg.d_model, MODEL)),
                "pos": P()}
    raise ValueError(cfg.kind)


# --------------------------------------------------------------------------
# activation sharding constraints (context-scoped; models stay mesh-agnostic)
# --------------------------------------------------------------------------
import contextlib as _ctx
import threading as _thr

_ACT = _thr.local()


@_ctx.contextmanager
def act_rules(rules: dict[str, P] | None):
    """Scope a dict of named activation constraints, e.g.
    {'residual': P(('data',), 'model', None)} for Megatron sequence-parallel
    residuals.  Models call ``constrain(x, 'residual')`` at layer boundaries."""
    prev = getattr(_ACT, "rules", None)
    _ACT.rules = rules
    try:
        yield
    finally:
        _ACT.rules = prev


def constrain(x, name: str):
    rules = getattr(_ACT, "rules", None)
    if rules and name in rules:
        return jax.lax.with_sharding_constraint(x, rules[name])
    return x


def rule_value(name: str, default=None):
    """Non-spec configuration riding the act-rules context (e.g. the MoE
    token-group size that keeps regrouping local to a shard)."""
    rules = getattr(_ACT, "rules", None)
    if rules and name in rules:
        return rules[name]
    return default


def default_act_rules(mesh: Mesh, step: str,
                      cfg: ArchConfig | None = None) -> dict[str, P]:
    """Sequence-parallel residuals for train/prefill; nothing for decode.

    MoE inner tensors: with n_experts % |model| == 0 the expert dim rides the
    model axis (EP); otherwise tokens ride data and the FFN hidden rides
    model (TP-inside-expert), with xe/ye 2-axis sharded (groups x d_model).
    """
    dp = data_axes(mesh)
    rules = {"logits": P(dp, None, MODEL),
             "pair": P(None, dp, MODEL, None),       # PPM (B, i, j, Hz)
             "seq_track": P(None, dp, None)}         # PPM (B, N, Hm)
    if step in ("train", "prefill"):
        rules["residual"] = P(dp, MODEL, None)       # (B, S, D): seq over model
    if cfg is not None and getattr(cfg, "moe", None):
        ep = cfg.moe.n_experts % _axis_size(mesh, MODEL) == 0
        if ep:
            rules["moe_tokens"] = P(dp, None, None)
            rules["moe_xe"] = P(dp, MODEL, None, None)       # experts on model
            rules["moe_hidden"] = P(MODEL, dp, None)         # (E, ng*C, f)
        else:
            rules["moe_tokens"] = P(dp, None, MODEL)
            rules["moe_xe"] = P(dp, None, None, MODEL)       # d_model on model
            rules["moe_hidden"] = P(None, dp, MODEL)         # f on model
    return rules


def opt_state_shardings(param_sh, mesh: Mesh):
    """AdamW moments shard exactly like their parameters (ZeRO-by-TP)."""
    return {"m": param_sh, "v": param_sh,
            "step": NamedSharding(mesh, P())}


def to_shardings(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree, is_leaf=lambda x: isinstance(x, P))


# --------------------------------------------------------------------------
# PPM
# --------------------------------------------------------------------------
def ppm_input_shardings(mesh: Mesh):
    """aatype (B, N): replicate batch (B=1), shard nothing — the pair tensor
    constraint inside the model does the work."""
    return {"aatype": P(None, data_axes(mesh))}


def ppm_constraints(mesh: Mesh):
    """with_sharding_constraint specs used inside the PPM forward."""
    return {
        "z": P(None, data_axes(mesh), MODEL, None),   # (B, i, j, Hz)
        "s": P(None, data_axes(mesh), None),          # (B, N, Hm)
    }


def ppm_serving_rules(mesh: Mesh) -> dict[str, P]:
    """Pair-representation act rules for the mesh-sharded serving tier.

    The serving engine lowers big-bucket executables under these: the pair
    tensor (B, i, j, Hz) rides the model axis on j — the dimension every
    Table-1 activation shares — so one block's per-device pair bytes drop
    by |model|, which is exactly what the admission controller's per-device
    pricing divides by.  Batch/i stay replicated: the long buckets this
    tier exists for run at batch 1-2, and the trunk's ``constrain`` calls
    at block boundaries re-pin the sharding so GSPMD keeps the triangular
    ops between them partitioned.  The sequence track (B, N, Hm) is linear
    in N and stays replicated (no rule = no constraint).  ``KERNEL_MESH``
    tells ``repro.kernels.dispatch`` to run Pallas calls per device under
    ``shard_map``, since GSPMD cannot partition a Mosaic kernel.
    """
    return {"pair": P(None, None, MODEL, None), KERNEL_MESH: mesh}
