"""Closed fold backlog: the generator every ``"kind": "backlog"`` mix uses.

An offline folding job keeps the server's queues full: the next batch is
always there when a chip frees up.  The mix's file gives the lengths of one
cycle, the buckets and the serving settings; this module turns them and a
seed into the batches the window serves.

* The lengths of a cycle are fixed by the file: ``lognormal_quantiles``
  takes the ``per_cycle`` mid-quantiles of a log-normal (``median``,
  ``log_sigma``) clipped to [``min``, ``max``]; ``even`` takes ``per_cycle``
  evenly spaced lengths from ``min`` to ``max``.
* Each bucket's share of a cycle is served as whole batches of the bucket's
  launch size.  The order of the buckets is a smooth weighted round robin,
  each bucket weighted by its batches per cycle, and is the same for every
  seed.  So every seed makes the server do the same padded work in the same
  order; the seed shuffles which lengths of a bucket go together and draws
  the residues.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

N_RESIDUE_TYPES = 20


def cycle_lengths(spec: dict) -> list[int]:
    """The lengths of one cycle, sorted."""
    n, lo, hi = spec["per_cycle"], spec["min"], spec["max"]
    if spec["dist"] == "lognormal_quantiles":
        z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
        out = [round(spec["median"] * math.exp(spec["log_sigma"] * v))
               for v in z]
        return sorted(min(hi, max(lo, v)) for v in out)
    if spec["dist"] == "even":
        return sorted(round(v) for v in np.linspace(lo, hi, n))
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def bucket_of(buckets, length: int) -> int:
    for b in sorted(buckets):
        if length <= b:
            return b
    raise ValueError(f"length {length} exceeds every bucket {buckets}")


def bucket_order(weights: dict[int, float]):
    """Smooth weighted round robin over buckets, ties to the smaller one:
    an endless, seed-free sequence in which each bucket's share of any
    prefix tracks its weight."""
    total = sum(weights.values())
    current = {b: 0.0 for b in weights}
    while True:
        for b in current:
            current[b] += weights[b]
        pick = max(sorted(current), key=lambda b: current[b])
        current[pick] -= total
        yield pick


def length(request: np.ndarray) -> int:
    """Residues of one request (an aatype array)."""
    return len(request)


class Backlog:
    """``next_batch()`` -> (bucket, [aatype arrays]) for one whole batch.

    ``caps`` maps each bucket to the launch size the server will use for
    it (its batch cap)."""

    def __init__(self, traffic: dict, seed: int, caps: dict[int, int]):
        self.buckets = tuple(sorted(traffic["buckets"]))
        self.caps = {b: caps[b] for b in self.buckets}
        self.rng = np.random.default_rng(seed)
        self.pool: dict[int, list[int]] = {b: [] for b in self.buckets}
        for n in cycle_lengths(traffic["lengths"]):
            self.pool[bucket_of(self.buckets, n)].append(n)
        self.pool = {b: v for b, v in self.pool.items() if v}
        weights = {b: len(v) / self.caps[b] for b, v in self.pool.items()}
        self.order = bucket_order(weights)
        self.queued: dict[int, list[int]] = {b: [] for b in self.pool}

    def _lengths(self, bucket: int, k: int) -> list[int]:
        q = self.queued[bucket]
        while len(q) < k:                  # a new cycle of this bucket
            q.extend(self.rng.permutation(self.pool[bucket]).tolist())
        out, self.queued[bucket] = q[:k], q[k:]
        return out

    def next_batch(self) -> tuple[int, list[np.ndarray]]:
        bucket = next(self.order)
        seqs = [self.rng.integers(0, N_RESIDUE_TYPES, n).astype(np.int32)
                for n in self._lengths(bucket, self.caps[bucket])]
        return bucket, seqs
