"""The engine readers of the program's spans (``engine_device_share``,
``distogram_fetch_ms_per_batch``, ``gc_ms_per_batch``) on hand-made runs:
what they read, that they take the window's batches only, and that each
reads nothing where its spans are missing, as on a program without
them."""
from __future__ import annotations

import pytest

from bench.metrics import (distogram_fetch_ms_per_batch, engine_device_share,
                           gc_ms_per_batch)
from bench.run import Batch, Run
from repro.serving.observability.tracing import Span

READERS = (engine_device_share, distogram_fetch_ms_per_batch,
           gc_ms_per_batch)


def batch_spans(seq: int, t: float, device: tuple, fetch: tuple,
                gc_s: tuple) -> list[Span]:
    """One batch's root spans on its track, from its dispatch at ``t``."""
    track = f"batch-{seq:04d}"

    def span(name, t0, t1, **attrs):
        return Span(0, None, name, "engine", track, t0, t1,
                    attrs={"batch_seq": seq, "bucket": 256, **attrs})

    return [span("dispatch", t, t + 0.01, gc_s=gc_s[0]),
            span("device", *device),
            span("retire", device[1] - 0.1, device[1] + 0.02, gc_s=gc_s[1]),
            span("fetch", *fetch, bytes=134_217_728)]


def hand_made_run(names=("dispatch", "device", "retire", "fetch")) -> Run:
    """Two batches in a 2 s window and a third retired after it."""
    spans = (batch_spans(0, 0.0, (0.005, 1.0), (1.02, 1.05), (1.0, 1.002))
             + batch_spans(1, 0.02, (1.0, 1.8), (1.82, 1.84), (1.0, 1.006))
             + batch_spans(2, 1.06, (1.8, 2.6), (2.62, 2.72), (1.2, 1.5)))
    spans.append(Span(0, None, "gc", "engine", "gc", 0.5, 0.503,
                      attrs={"generation": 2, "collected": 10}))
    return Run(cfg={}, peak=None,
               window=[Batch(256, 4, 0, []), Batch(256, 4, 0, [])],
               window_s=2.0,
               spans=[s for s in spans if s.name in names + ("gc",)])


def test_device_share_sums_the_window_batches_device_spans():
    assert engine_device_share.read(hand_made_run()) == pytest.approx(
        100.0 * (0.995 + 0.8) / 2.0)


def test_fetch_ms_per_window_batch():
    assert distogram_fetch_ms_per_batch.read(hand_made_run()) == \
        pytest.approx(1e3 * (0.03 + 0.02) / 2)


def test_gc_ms_from_first_dispatch_to_last_retire():
    assert gc_ms_per_batch.read(hand_made_run()) == pytest.approx(
        1e3 * (1.006 - 1.0) / 2)


@pytest.mark.parametrize("reader", READERS,
                         ids=[r.__name__.split(".")[-1] for r in READERS])
def test_reads_nothing_without_its_spans(reader):
    # the spans of a program that records neither device nor fetch spans
    # nor the gc counter: dispatch and retire without ``gc_s``
    parent = hand_made_run(names=("dispatch", "retire"))
    for s in parent.spans:
        s.attrs.pop("gc_s", None)
    assert reader.read(parent) is None
    assert reader.read(Run(cfg={}, peak=None)) is None
    empty_window = hand_made_run()
    empty_window.window = []
    assert reader.read(empty_window) is None
