"""Bridge from the serving tracer to the JAX/XLA profiler.

The span tracer times *host-side* phases; the XLA profiler sees *device*
kernels.  To line the two up, the Tracer opens ``annotate(...)`` — a
``jax.profiler.TraceAnnotation`` when the profiler API is available (a
cheap no-op context otherwise) — around the engine's batch phases, so a
``--jax-profile DIR`` run shows them as named ranges inside the XLA
timeline, alongside the kernels they launched.  The Tracer is its only
caller (``Tracer.annotate``).

``jax_profile(dir)`` is the run-level context the launchers use: start a
JAX profiler trace into ``dir`` (open with TensorBoard or Perfetto), stop
it on exit, and degrade to a no-op when profiling is unavailable or
``dir`` is falsy.
"""
from __future__ import annotations

import contextlib

_ANNOTATION = None
_CHECKED = False


def _annotation_cls():
    """Resolve jax.profiler.TraceAnnotation once (None = unavailable)."""
    global _ANNOTATION, _CHECKED
    if not _CHECKED:
        _CHECKED = True
        try:
            from jax.profiler import TraceAnnotation
            _ANNOTATION = TraceAnnotation
        except Exception:       # profiler API absent/moved: stay a no-op
            _ANNOTATION = None
    return _ANNOTATION


def annotate(name: str, **stats):
    """Context manager naming the enclosed host work ``name`` in XLA
    profiler traces, with ``stats`` as the event's stats (the event keeps
    its plain name); a no-op context when the profiler API is
    unavailable."""
    cls = _annotation_cls()
    return contextlib.nullcontext() if cls is None else cls(name, **stats)


@contextlib.contextmanager
def jax_profile(log_dir: str | None):
    """Run-level JAX profiler capture into ``log_dir`` (no-op when falsy
    or the profiler cannot start — e.g. another trace is active)."""
    if not log_dir:
        yield False
        return
    import jax
    try:
        jax.profiler.start_trace(log_dir)
    except Exception as e:          # pragma: no cover - env-dependent
        print(f"# jax-profile disabled ({e!r})")
        yield False
        return
    try:
        yield True
    finally:
        jax.profiler.stop_trace()
