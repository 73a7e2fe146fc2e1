"""Pipeline parallelism over the pod axis (GPipe schedule, shard_map).

Multi-pod placement alternative to pure DP: cross-pod links are the slow
tier, so instead of an all-reduce of full gradients every step (DP-over-pod)
each pod owns a contiguous *stage* of the layer stack and only microbatch
activations cross pods (ppermute) — bytes per step drop from O(params) to
O(n_micro x mb x S x D).

Schedule: classic GPipe fill-drain over ``n_micro + n_stages - 1`` ticks.
Bubble fraction = (p-1)/(n_micro + p - 1); §Perf quantifies DP-vs-PP on the
multi-pod collective term.

The stage stack must be homogeneous (scan-stacked blocks): the block
params' leading layer axis is sharded over 'pod', each stage applying its
local L/p layers. Embedding/unembed run replicated (they are small relative
to the stack for the archs where PP matters).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import transformer as tf


def _apply_local_stack(blocks_local, x, cfg, positions, block_fn):
    def body(carry, p):
        y, _ = block_fn(p, carry, cfg, positions=positions)
        return y, None
    y, _ = jax.lax.scan(body, x, blocks_local)
    return y


def gpipe_apply(blocks, x, cfg, *, mesh, n_micro: int, block_fn=None,
                axis: str = "pod"):
    """x: (B, S, D) embedded activations (replicated over ``axis``);
    blocks: scan-stacked params with leading layer dim sharded over ``axis``.
    Returns final activations (B, S, D)."""
    block_fn = block_fn or tf.block_apply
    p = mesh.shape[axis]
    b, s, d = x.shape
    assert b % n_micro == 0
    mb = b // n_micro
    positions = jnp.broadcast_to(jnp.arange(s)[None], (mb, s))

    def fn(blocks_local, xr):
        stage = jax.lax.axis_index(axis)
        xmb = xr.reshape(n_micro, mb, s, d)
        perm = [(i, i + 1) for i in range(p - 1)]
        recv = jnp.zeros((mb, s, d), xr.dtype)
        outs = jnp.zeros((n_micro, mb, s, d), xr.dtype)
        for t in range(n_micro + p - 1):
            mb_in = jnp.clip(t, 0, n_micro - 1)
            mb_out = t - (p - 1)
            inp = jnp.where(stage == 0, xmb[mb_in], recv)
            active = jnp.logical_and(stage <= t, t - stage < n_micro)
            y = _apply_local_stack(blocks_local, inp, cfg, positions,
                                   block_fn)
            y = jnp.where(active, y, 0.0)
            if 0 <= mb_out:
                take = jnp.logical_and(stage == p - 1, active)
                outs = outs.at[jnp.clip(mb_out, 0, n_micro - 1)].add(
                    jnp.where(take, y, 0.0))
            recv = jax.lax.ppermute(y, axis, perm)
        # broadcast the last stage's outputs to every pod
        outs = jax.lax.psum(outs, axis) / 1.0
        return outs.reshape(b, s, d)

    in_specs = (P(axis), P())          # blocks: layer dim over pods
    out_specs = P()
    fn_sm = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)
    return fn_sm(blocks, x)


def gpipe_loss(params, batch, cfg, *, mesh, n_micro: int = 4,
               axis: str = "pod"):
    """Dense-LM loss with the block stack pipelined over ``axis``."""
    x = tf._embed_inputs(params, batch, cfg)
    x = gpipe_apply(params["blocks"], x, cfg, mesh=mesh, n_micro=n_micro,
                    axis=axis)
    x = tf.apply_norm(params["final_norm"], x, cfg)
    return tf.chunked_xent(params, x, batch["labels"], cfg)
