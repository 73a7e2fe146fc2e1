"""The comparison that decides a run's ``correct``.

Once the window has closed, a sample of the folds the server finished is
drawn from the seed: the longest, the last row of a batch of several, and
others at random.  The plain reference of the configuration's model
(``Reference`` of ``bench/models/<model>.py``) folds each sampled request
alone, padded to the bucket it was served in, and each served fold is
held against it by four numbers, each the worst over the sample:

* ``tm_gap``: 1 - TM-score of the served C-alpha trace against the
  reference's, after optimal superposition (the paper's fidelity measure);
* ``coords_rel_rms``: root-mean-square coordinate difference over the
  reference's root-mean-square distance from its centroid;
* ``disto_rel_rms``: root-mean-square distogram-logit difference over the
  standard deviation of the reference's logits;
* ``disto_bin_mismatch``: share of residue pairs whose most likely
  distance bin differs.

A configuration compares the numbers its file gives a limit for
(``"limits"``); ``PERF.md`` gives the readings each limit was set from.
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("tm_gap", "coords_rel_rms", "disto_rel_rms", "disto_bin_mismatch")


def sample(batches: list[list], seed: int, k: int) -> list:
    """``k`` finished folds drawn from ``seed``: the longest, the last row
    of a batch of several (where a batch that lost rows shows), and the
    rest at random.  ``batches``: each served batch's folds in row order."""
    folds = [r for b in batches for r in b]
    if not folds:
        return []
    rng = np.random.default_rng([seed, 2])
    picked = [max(folds, key=lambda r: (r.length, -r.request_id))]
    lasts = [b[-1] for b in batches
             if len(b) > 1 and b[-1].request_id != picked[0].request_id]
    if lasts and k > 1:
        picked.append(lasts[rng.integers(len(lasts))])
    ids = {r.request_id for r in picked}
    rest = [r for r in folds if r.request_id not in ids]
    n = min(k - len(picked), len(rest))
    picked += [rest[i] for i in sorted(rng.choice(len(rest), n,
                                                  replace=False))]
    return picked


def kabsch(p: np.ndarray, q: np.ndarray) -> np.ndarray:
    """``p`` superposed onto ``q`` (both (N, 3)) by the best rotation."""
    pc, qc = p - p.mean(0), q - q.mean(0)
    u, _, vt = np.linalg.svd(pc.T @ qc)
    d = np.sign(np.linalg.det(vt.T @ u.T))
    rot = vt.T @ np.diag([1.0, 1.0, d]) @ u.T
    return pc @ rot.T + q.mean(0)


def tm_score(p: np.ndarray, q: np.ndarray) -> float:
    """TM-score of trace ``p`` against reference ``q``, d0 from the length
    (floored at 0.5 for short chains)."""
    n = q.shape[0]
    d0 = max(1.24 * np.cbrt(max(n - 15.0, 1.0)) - 1.8, 0.5)
    d = np.sqrt(np.sum((kabsch(p, q) - q) ** 2, axis=-1))
    return float(np.mean(1.0 / (1.0 + (d / d0) ** 2)))


def readings(coords, disto, ref_coords, ref_disto) -> dict[str, float]:
    """The four numbers of one fold against its reference."""
    c = np.asarray(coords, np.float64)
    rc = np.asarray(ref_coords, np.float64)
    d = np.asarray(disto, np.float64)
    rd = np.asarray(ref_disto, np.float64)
    spread = np.sqrt(np.mean(np.sum((rc - rc.mean(0)) ** 2, axis=-1)))
    return {
        "tm_gap": 1.0 - tm_score(c, rc),
        "coords_rel_rms": float(np.sqrt(np.mean(np.sum((c - rc) ** 2, -1)))
                                / spread),
        "disto_rel_rms": float(np.sqrt(np.mean((d - rd) ** 2)) / np.std(rd)),
        "disto_bin_mismatch": float(np.mean(np.argmax(d, -1)
                                            != np.argmax(rd, -1))),
    }


def distogram(result) -> np.ndarray:
    """A served fold's (L, L, bins) distogram on the host."""
    return np.asarray(result.distogram, np.float32)


def worst(per_fold: list[dict]) -> dict[str, float]:
    return {k: max(r[k] for r in per_fold) for k in NUMBERS}


def compare(cfg: dict, params, outputs: list) -> tuple[dict, dict]:
    """``outputs``: (request, bucket, coords, distogram) of each sampled
    fold; the model's reference folds the request padded to its bucket.
    Returns (checks: {number: {"value", "limit"}} for the numbers the
    configuration limits, readings: every number)."""
    from bench.run import model_of
    if not outputs:
        return {}, {}
    ref = model_of(cfg).Reference(cfg)
    per_fold = []
    for request, bucket, coords, disto in outputs:
        rc, rd = ref(params, request, bucket)
        per_fold.append(readings(coords, disto, rc, rd))
    got = worst(per_fold)
    checks = {k: {"value": got[k], "limit": v}
              for k, v in cfg.get("limits", {}).items()}
    return checks, got
