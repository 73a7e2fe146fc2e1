"""The window's batches in the engine's spans, for the engine readers:
the first ``len(run.window)`` batches dispatched after the tracer's reset,
as ``engine_host_ms_per_batch`` takes them.  Not a metric."""


def batch_seqs(run) -> list[int]:
    """The window batches' ``batch_seq``s, in dispatch order."""
    seqs = sorted(s.attrs["batch_seq"] for s in run.spans
                  if s.name == "dispatch" and "batch_seq" in s.attrs)
    return seqs[:len(run.window)]


def spans_of(run, name: str) -> list | None:
    """The closed ``name`` span of each window batch, in order; None
    when a window batch has none (a program that does not record it)."""
    by_seq = {s.attrs.get("batch_seq"): s for s in run.spans
              if s.name == name and s.duration is not None}
    seqs = batch_seqs(run)
    if not seqs or any(q not in by_seq for q in seqs):
        return None
    return [by_seq[q] for q in seqs]
