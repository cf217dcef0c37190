"""The trace reduction: busy time as a union, idle gaps labelled by the
benchmark's host spans, kernel time by name; checked by hand on made-up
intervals and on a small trace recorded on one v5e chip."""

from __future__ import annotations

import gzip
from pathlib import Path

import pytest

from chip import kernels, tracing

# Three tgn-wiki.train steps in a bench/window span, recorded on one TPU
# v5e by the benchmark's own profiler options, gzipped.
FIXTURE = (Path(__file__).resolve().parent / "fixtures"
           / "tgn_train_steps.xplane.pb.gz")


def test_busy_is_the_union_of_device_intervals_within_the_window():
    spans = [("bench/window", 100, 200), ("bench/wait", 100, 130),
             ("bench/step", 130, 150), ("bench/wait", 150, 195)]
    # Two overlapping ops, one op half outside the window, one outside.
    ops = [("a", 110, 140), ("b", 120, 145), ("c", 180, 260), ("d", 10, 20)]
    r = tracing.reduce_events(spans, [ops])
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx((35 + 20) * 1e-9)
    assert r.op_seconds == pytest.approx({"a": 30e-9, "b": 25e-9,
                                          "c": 20e-9})
    # Idle: [100, 110) in wait, [145, 180) mostly in the second wait.
    assert r.gaps == [("bench/wait", pytest.approx(35e-9)),
                      ("bench/wait", pytest.approx(10e-9))]
    assert r.span_seconds["bench/step"] == pytest.approx(20e-9)


def test_busy_is_averaged_over_the_devices_that_ran():
    spans = [("bench/window", 0, 100)]
    r = tracing.reduce_events(spans, [[("x", 0, 50)], [("x", 0, 100)], []])
    assert r.devices == 2
    assert r.busy_s == pytest.approx(75e-9)
    assert r.op_seconds["x"] == pytest.approx(75e-9)


def test_a_trace_without_the_window_span_is_an_error():
    with pytest.raises(ValueError, match="bench/window"):
        tracing.reduce_events([("bench/wait", 0, 1)], [])


def test_op_names_keep_the_instruction_and_mark_kernels():
    assert tracing._op_name("%fusion.6 = f32[8]{0} fusion(%x)") == "fusion.6"
    text = ('%jvp__.1 = f32[640,128]{1,0} custom-call(%a), '
            'custom_call_target="tpu_custom_call"')
    assert tracing._op_name(text) == "jvp__.1" + tracing.PALLAS
    assert kernels.is_attention_kernel(tracing._op_name(text))


def test_trace_recorded_on_the_chip(tmp_path):
    assert FIXTURE.stat().st_size < 1_000_000
    path = tmp_path / "trace.xplane.pb"
    path.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    r = tracing.reduce(str(path))
    assert r.devices == 1
    assert 0 < r.busy_s <= r.window_s
    assert r.seconds_of(kernels.is_attention_kernel) > 0
    assert {"bench/wait", "bench/step"} <= set(r.span_seconds)
    assert r.gaps and all(label.startswith("bench/") or label ==
                          "outside benchmark spans" for label, _ in r.gaps)
    assert sorted((g for _, g in r.gaps), reverse=True) == [g for _, g in r.gaps]
