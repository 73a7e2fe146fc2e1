"""Model: FLOPs of the folds retired in the window at their real lengths
(``bench/ops/ppm.py``, FP-equivalent) over the window's seconds, as a %
of the chip's bfloat16 peak."""

from bench.ops.ppm import fold_flops


def read(run):
    if not run.window_s or not run.peak:
        return None
    flops = sum(fold_flops(run.cfg, f.length)
                for b in run.window for f in b.results if f.ok)
    return 100.0 * flops / run.window_s / run.peak["bf16_flops_s"]
