"""Engine: milliseconds of garbage collection per window batch: the
process's cumulative pause counter (``gc_s``) at the last window batch's
``retire`` end less at the first window batch's ``dispatch`` start."""

from bench.metrics.window_batches import spans_of


def read(run):
    dispatch = spans_of(run, "dispatch")
    retire = spans_of(run, "retire")
    if dispatch is None or retire is None:
        return None
    first = dispatch[0].attrs.get("gc_s")
    last = retire[-1].attrs.get("gc_s")
    if first is None or last is None:
        return None
    return 1e3 * (last - first) / len(dispatch)
