"""Fold-model parameters from a seed, made on the device in one jitted call.

The tree has the layout the fold server loads (the nested ``w``/``b``/
``g``/``e`` dicts of its checkpoints, the trunk's blocks stacked on a
leading axis), in the configuration's serving dtype.  Both the server and
the plain reference read this one tree, so neither takes its weights from
the other.  Weights are random: dense kernels normal with scale
1/sqrt(fan_in), embeddings normal with scale 0.02, layer-norm gains 1 and
every bias 0, the structure module's distance weights 0.1.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp


def _dense(i: int, o: int, bias: bool = False) -> dict:
    spec = {"w": ("normal", (i, o), 1.0 / math.sqrt(i))}
    if bias:
        spec["b"] = ("zeros", (o,))
    return spec


def _ln(d: int) -> dict:
    return {"g": ("ones", (d,)), "b": ("zeros", (d,))}


def _block(cfg: dict) -> dict:
    hm, hz, th = cfg["hm"], cfg["hz"], cfg["tri_hidden"]
    f = cfg["transition_factor"]

    def tri_mul():
        return {"ln_in": _ln(hz), "a_proj": _dense(hz, th),
                "a_gate": _dense(hz, th), "b_proj": _dense(hz, th),
                "b_gate": _dense(hz, th), "ln_out": _ln(th),
                "out": _dense(th, hz), "out_gate": _dense(hz, hz)}

    def tri_attn():
        return {"ln": _ln(hz), "qkv": _dense(hz, 3 * hz),
                "bias": _dense(hz, cfg["pair_heads"]),
                "gate": _dense(hz, hz), "out": _dense(hz, hz)}

    return {
        "seq_attn": {"ln": _ln(hm), "qkv": _dense(hm, 3 * hm, True),
                     "pair_bias_ln": _ln(hz),
                     "pair_bias": _dense(hz, cfg["seq_heads"]),
                     "gate": _dense(hm, hm), "out": _dense(hm, hm)},
        "seq_trans": {"ln": _ln(hm), "up": _dense(hm, f * hm, True),
                      "down": _dense(f * hm, hm, True)},
        "opm": {"ln": _ln(hm), "a": _dense(hm, 32), "b": _dense(hm, 32),
                "out": _dense(32 * 32, hz, True)},
        "tri_mul_out": tri_mul(), "tri_mul_in": tri_mul(),
        "tri_attn_start": tri_attn(), "tri_attn_end": tri_attn(),
        "pair_trans": {"ln": _ln(hz), "up": _dense(hz, f * hz, True),
                       "down": _dense(f * hz, hz, True)},
    }


def param_spec(cfg: dict) -> dict:
    """The parameter tree as (kind, shape[, scale]) leaves."""
    hm, hz = cfg["hm"], cfg["hz"]
    stacked = jax.tree.map(
        lambda leaf: (leaf[0], (cfg["blocks"], *leaf[1]), *leaf[2:]),
        _block(cfg), is_leaf=lambda x: isinstance(x, tuple))
    return {
        "aa_embed": {"e": ("normal", (cfg["vocab"], hm), 0.02)},
        "left": _dense(hm, hz), "right": _dense(hm, hz),
        "relpos": {"e": ("normal", (cfg["relpos_bins"], hz), 0.02)},
        "recycle_s_ln": _ln(hm), "recycle_z_ln": _ln(hz),
        "trunk": stacked,
        "structure": {
            "ln_s": _ln(hm), "ln_z": _ln(hz),
            "qkv": _dense(hm, 3 * hm, True),
            "pair_bias": _dense(hz, cfg["seq_heads"]),
            "out": _dense(hm, hm),
            "trans_mlp": {"ln": _ln(hm), "up": _dense(hm, 2 * hm, True),
                          "down": _dense(2 * hm, hm, True)},
            "coord_ln": _ln(hm), "coord": _dense(hm, 3, True),
            "dist_w": ("full", (cfg["seq_heads"],), 0.1),
        },
        "distogram": _dense(hz, cfg["distogram_bins"], True),
    }


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed below 2**64."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


SIZE_KEYS = ("blocks", "hm", "hz", "seq_heads", "pair_heads", "tri_hidden",
             "transition_factor", "vocab", "relpos_bins", "distogram_bins",
             "dtype")


@functools.cache
def _maker(sizes_json: str):
    """The jitted key -> parameters function for one set of sizes."""
    cfg = json.loads(sizes_json)
    leaves, tree = jax.tree.flatten(param_spec(cfg),
                                    is_leaf=lambda x: isinstance(x, tuple))
    dt = jnp.dtype(cfg["dtype"])

    def one(k, leaf):
        kind, shape = leaf[0], leaf[1]
        if kind == "normal":
            return (jax.random.normal(k, shape, jnp.float32)
                    * leaf[2]).astype(dt)
        if kind == "full":
            return jnp.full(shape, leaf[2], dt)
        return (jnp.ones if kind == "ones" else jnp.zeros)(shape, dt)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(tree, [one(k, leaf)
                                         for k, leaf in zip(keys, leaves)])

    return make


def make_params(cfg: dict, seed: int):
    """The fold model's parameters for ``cfg`` from ``seed``, on the
    default device, in ``cfg["dtype"]``."""
    sizes = json.dumps({k: cfg[k] for k in SIZE_KEYS}, sort_keys=True)
    return _maker(sizes)(seed_key(seed))
