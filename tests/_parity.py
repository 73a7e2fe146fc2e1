"""The numerics contract for one fold served two ways.

Batched and solo, padded to different launch sizes, in process and over
HTTP: each pair runs a different executable, and XLA does not promise
that two executables reduce in the same order.  So no bitwise equality is
asserted between them.  The contract is the one the sharded tier already
gates on (``test_placement.py``):

  * FP schemes: coords allclose at ``FP_RTOL`` / ``FP_ATOL``;
  * AAQ: a reduction-order difference can move a value across a
    quantization bin and the change grows through the trunk, so the gate
    is the paper's fidelity metric, TM-score >= ``AAQ_MIN_TM``.

TM-score pins its distance scale d0 at its 0.5 floor below about 22
residues, where it reads a 0.1 coordinate wobble as a structural change;
folds gated here are 40 residues or longer, as in the sharded-tier gate.
Checks that re-run one executable (depth parity, cache hits) stay bitwise.
"""
import jax.numpy as jnp
import numpy as np

from repro.core import make_scheme
from repro.models.ppm import tm_score

FP_RTOL = FP_ATOL = 1e-4
AAQ_MIN_TM = 0.995


def assert_fold_parity(scheme, got, want) -> None:
    """``got``/``want``: (N, 3) real-token coords of the same sequence."""
    name = scheme if isinstance(scheme, str) else scheme.name
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.isfinite(got).all() and np.isfinite(want).all()
    if make_scheme(name).name == "baseline_fp16":
        np.testing.assert_allclose(got, want, rtol=FP_RTOL, atol=FP_ATOL)
    else:
        tm = float(tm_score(jnp.asarray(got), jnp.asarray(want)))
        assert tm >= AAQ_MIN_TM, (name, tm)
