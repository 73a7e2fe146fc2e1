"""Engine: the share of the window's seconds in which the chip worked on
a window batch, in %: the window batches' ``device`` spans (from the later
of a batch's launch and the previous batch's ready time to its own ready
time) over the window."""

from bench.metrics.window_batches import spans_of


def read(run):
    spans = spans_of(run, "device")
    if spans is None or not run.window_s:
        return None
    return 100.0 * sum(s.duration for s in spans) / run.window_s
