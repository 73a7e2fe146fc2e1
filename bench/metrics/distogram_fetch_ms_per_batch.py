"""Engine: milliseconds per window batch in the distogram's one
device-to-host copy (the batch's ``fetch`` span, which the consumer's
first distogram read opens)."""

from bench.metrics.window_batches import spans_of


def read(run):
    spans = spans_of(run, "fetch")
    if spans is None:
        return None
    return 1e3 * sum(s.duration for s in spans) / len(spans)
