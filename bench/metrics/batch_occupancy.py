"""Scheduler: real residues over launched rows x bucket, in % (the
``FoldResult.occupancy`` and ``launched_batch`` counts of the window's
batches)."""


def read(run):
    real = sum(f.length for b in run.window for f in b.results)
    rows = sum(b.launched * b.bucket for b in run.window)
    return 100.0 * real / rows if rows else None
