"""Communication/compute overlap primitives (shard_map level).

``ring_ag_matmul``: y = all_gather(x, axis) @ w computed as a ring — each
step matmuls the resident shard while ppermute moves the next one, so the
collective hides behind the MXU.  This is the manual form of XLA's
latency-hiding-scheduler collective-matmul; having it as an explicit
primitive lets §Perf compare "exposed all-gather" vs "overlapped ring" on
the collective roofline term (the ring's permutes total the same bytes but
zero *exposed* time when per-step matmul >= per-step permute).

``psum_scatter_matmul``: the row-parallel dual — local matmul emitted in
ring order, reduce-scattered chunk by chunk.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ring_ag_matmul(x, w, axis_name: str):
    """x: (m, k/p) local shard; w: (k/p, n) matching local rows of the
    weight; computes all_gather(x) @ w_full without materializing the
    gather.  Must run inside shard_map with ``axis_name``."""
    p = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % p) for i in range(p)]

    def body(i, carry):
        acc, blk = carry
        # owner of `blk` at step i: (idx - i) mod p -> selects w rows
        src = (idx - i) % p
        acc = acc + jnp.einsum("mk,kn->mn", blk,
                               jax.lax.dynamic_index_in_dim(w_stacked, src,
                                                            keepdims=False))
        blk = jax.lax.ppermute(blk, axis_name, perm)
        return acc, blk

    k_local, n = w.shape
    w_stacked = jax.lax.all_gather(w, axis_name)       # (p, k/p, n) resident
    acc0 = jnp.zeros((x.shape[0], n), jnp.float32)
    acc, _ = jax.lax.fori_loop(0, p, body, (acc0, x.astype(jnp.float32)))
    return acc


def ring_ag_matmul_ws(x, w_full, axis_name: str):
    """Weight-stationary variant: w_full (k, n) is already resident
    (parameters); x (m, k/p) is the sharded activation.  Each ring step
    consumes one k-shard of w — no weight gather at all."""
    p = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % p) for i in range(p)]
    k = w_full.shape[0]
    kl = k // p

    def body(i, carry):
        acc, blk = carry
        src = (idx - i) % p
        wsh = jax.lax.dynamic_slice_in_dim(w_full, src * kl, kl, axis=0)
        acc = acc + jnp.dot(blk.astype(jnp.float32), wsh.astype(jnp.float32))
        blk = jax.lax.ppermute(blk, axis_name, perm)
        return acc, blk

    acc0 = jnp.zeros((x.shape[0], w_full.shape[1]), jnp.float32)
    acc, _ = jax.lax.fori_loop(0, p, body, (acc0, x))
    return acc


def psum_scatter_matmul(x, w, axis_name: str):
    """Row-parallel linear with overlapped reduction:
    x (m, k_local), w (k_local, n) -> reduce_scattered (m/p, n) result."""
    y = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))
    return jax.lax.psum_scatter(y, axis_name, scatter_dimension=0,
                                tiled=True)
