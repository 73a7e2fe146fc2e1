"""The trace reduction: hand-made traces, and a small trace recorded on a
TPU v5e (``bench/testdata/small.xplane.pb``: a 2-block fold model at the
published widths, AAQ, two batches of 2 x 128 residues)."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace

RECORDED = Path(__file__).resolve().parent / "testdata" / "small.xplane.pb"


def test_union_merges_overlaps():
    assert trace.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [
        (0, 2.5), (3, 4)]


def test_busy_idle_kernels_and_gaps():
    ops = [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 1.0, 2.0),
           ("%flash_mha_pallas.7 = bf16[2]{0} custom-call(bf16[2]{0} "
            "%fusion.1)", 1.5, 3.0),
           ("%while.2 = (s32[], bf16[2]{0}) while((s32[], bf16[2]) %t)",
            0.5, 6.0),                                    # holds the others
           ("%aaq_quantize_pallas.9 = (s8[2,1]{1,0}) custom-call(bf16[2,2] "
            "%flash_mha_pallas.7)", 5.0, 6.0),
           ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 9.0,
            12.0)]                                        # runs past the end
    host = [("serve.dispatch/256", 3.2, 4.9), ("serve.retire/256", 6.0, 8.0),
            ("bench.window", 0.0, 10.0)]
    r = trace.reduce(ops, host, window=(0.0, 10.0))
    assert r.window_s == 10.0
    assert r.busy_s == pytest.approx(5.5 + 1.0)
    # a kernel is matched by its own name, not by an operand's
    assert r.kernel_s == {"flash_mha_pallas": 1.5,
                          "aaq_quantize_pallas": 1.0}
    ops = dict(r.ops)
    assert ops["fusion.1 f32[8] fusion"] == pytest.approx(2.0)
    assert not any(n.startswith("while") for n in ops)
    gaps = r.idle_gaps
    assert [g[0] for g in gaps] == ["serve.retire/256", "host:other"]
    assert [g[1] for g in gaps] == pytest.approx([3.0, 0.5])
    assert set(r.breakdown()) == {"device_ops", "idle_gaps"}


def test_empty_trace_reads_nothing():
    r = trace.reduce([], [], None)
    assert r.busy_s == 0.0 and r.window_s == 0.0 and r.kernel_s == {}


def test_recorded_trace():
    """Two batches of 2 x 128 residues through a 2-block model: every
    kernel call the served path makes is in the trace, by name."""
    ops, host, window = trace.read_xplane(str(RECORDED))
    assert window is not None and window[1] > window[0]
    r = trace.reduce(ops, host, window)
    calls = {k: sum(v.values()) for k, v in r.kernel_calls.items()}
    # flash: 2 triangular + 1 sequence attention a block, 4 in the
    # structure module; a batch quantizes 14 distinct (input, policy)
    # pairs a block for its 22 AAQ linears, whose matmuls all run
    assert calls == {"flash_mha_pallas": 2 * (2 * 3 + 4),
                     "aaq_quantize_pallas": 2 * 2 * 14,
                     "aaq_matmul_pallas": 2 * 2 * 22}
    assert 0 < r.busy_s < r.window_s
    assert sum(r.kernel_s.values()) < r.busy_s
    assert r.ops[0][0] == "aaq_quantize_pallas"
    assert {g[0] for g in r.idle_gaps[:3]} <= {"serve.dispatch/128",
                                               "serve.retire/128"}
    assert set(r.breakdown()) == {"device_ops", "idle_gaps"}
    assert len(r.breakdown()["device_ops"]) == trace.TOP
