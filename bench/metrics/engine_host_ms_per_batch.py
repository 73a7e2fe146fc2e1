"""Engine: host milliseconds per window batch in the engine's own spans
(``resolve_executable``, ``pad``, ``device_put``, ``launch``, ``transfer``)."""

HOST_SPANS = ("resolve_executable", "pad", "device_put", "launch",
              "transfer")


def read(run):
    n = len(run.window)
    if not n:
        return None
    threads = {s.thread for s in run.spans if s.name == "dispatch"}
    seqs = sorted(threads)[:n]          # the window's batches, in order
    host = sum(s.duration for s in run.spans
               if s.name in HOST_SPANS and s.thread in seqs
               and s.duration is not None)
    return 1e3 * host / n
