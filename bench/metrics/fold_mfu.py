"""Model: FLOPs of the folds retired in the window at their real lengths
(the model module's ``fold_flops``, FP-equivalent) over the window's
seconds, as a % of the chip's bfloat16 peak."""

from bench.run import model_of


def read(run):
    if not run.window_s or not run.peak:
        return None
    fold_flops = model_of(run.cfg).fold_flops
    flops = sum(fold_flops(run.cfg, f.length)
                for b in run.window for f in b.results if f.ok)
    return 100.0 * flops / run.window_s / run.peak["bf16_flops_s"]
