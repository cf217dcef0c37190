"""The program's spans and device programs in a trace (``program.py``):
seconds clipped to the window, module seconds by name, idle gaps labelled
with what each host thread was doing, checked by hand on made-up intervals
and on the trace recorded on one v5e chip; ``spans.py`` rehearsed on the
CPU."""

from __future__ import annotations

import gzip
import json

import pytest

from chip import program, spans, tracing
from chip.rehearse import SCALE
from chip.test_chip_tracing import FIXTURE

BENCH = [("bench/window", 100, 200), ("bench/wait", 100, 130),
         ("bench/step", 130, 150), ("bench/wait", 150, 195)]
# Device ops leave [100, 110) and [145, 180) idle in the window.
OPS = [("a", 110, 140), ("b", 120, 145), ("c", 180, 260)]
MAIN, PRODUCER = (0, 1), (0, 2)


def test_span_seconds_are_clipped_to_the_window():
    prog = program.Program(spans=[
        ("hooks", 90, 120, PRODUCER), ("hooks/A", 95, 110, PRODUCER),
        ("hooks", 150, 260, PRODUCER), ("loader/wait", 100, 130, MAIN)],
        modules=[])
    got = program.span_seconds(prog, 100, 200)
    assert got == pytest.approx({"hooks": 70e-9, "hooks/A": 10e-9,
                                 "loader/wait": 30e-9})


def test_module_seconds_by_name_averaged_over_devices():
    prog = program.Program(spans=[], modules=[
        [("jit_sampler_update", 90, 110), ("jit_train_step", 110, 190)],
        [("jit_sampler_update", 100, 104)], []])
    got = program.module_seconds(prog, 100, 200)
    assert got == pytest.approx({"jit_sampler_update": 7e-9,
                                 "jit_train_step": 40e-9})
    assert program.module_name("jit_sampler_sample(2728770)") == \
        "jit_sampler_sample"


def test_gap_labels_are_tracing_labels_without_program_spans():
    none = program.Program(spans=[], modules=[])
    got = program.label_gaps(BENCH, [OPS], none)
    assert got == tracing.reduce_events(BENCH, [OPS]).gaps


def test_gap_labels_name_the_innermost_span_of_each_thread():
    prog = program.Program(spans=[
        # Main thread: the consumer waits through the second gap.
        ("loader/wait", 148, 194, MAIN),
        # Producer: hooks hold both gaps, one hook most of the second.
        ("hooks", 100, 190, PRODUCER),
        ("hooks/Recency", 150, 178, PRODUCER),
        ("hooks/Neg", 178, 182, PRODUCER)], modules=[])
    got = program.label_gaps(BENCH, [OPS], prog)
    assert [label for label, _ in got] == [
        "bench/wait > loader/wait | hooks/Recency",
        "bench/wait > hooks"]
    assert [s for _, s in got] == pytest.approx([35e-9, 10e-9])


def test_readings_per_step_and_what_reads_nothing():
    prog = program.Program(spans=[
        ("hooks", 100, 180, PRODUCER), ("loader/stage", 180, 190, PRODUCER),
        ("loader/wait", 100, 110, MAIN)],
        modules=[[("jit_sampler_sample", 120, 124),
                  ("jit_sampler_update", 130, 132),
                  ("jit_train_step", 140, 180)]])
    got = program.readings("train", prog, BENCH, steps=2, compiles=0)
    ms = 1e-9 * 1e3 / 2
    assert got == pytest.approx({
        "hooks_ms.train": 80 * ms, "stage_ms.train": 10 * ms,
        "loader_wait_ms.train": 10 * ms, "sampler_ms.train": 6 * ms,
        "compiles.train": 0})
    # No device plane (a CPU trace), no spans: nothing is read.
    empty = program.Program(spans=[], modules=[])
    assert program.readings("eval", empty, BENCH, 2, None) == {}


def test_trace_recorded_on_the_chip(tmp_path):
    path = tmp_path / "trace.xplane.pb"
    path.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    bench_spans, devices = tracing.load(str(path))
    prog = program.load(str(path))
    # Recorded before the program's spans reached the trace.
    assert prog.spans == []
    assert program.label_gaps(bench_spans, devices, prog) == \
        tracing.reduce(str(path)).gaps
    w0, w1 = program.window(bench_spans)
    mods = program.module_seconds(prog, w0, w1)
    assert mods["jit_train_step"] > 0
    # Each module event holds op events of the same device.
    busy = tracing.reduce(str(path)).busy_s
    assert 0 < mods["jit_train_step"] <= sum(mods.values()) <= 1.01 * busy


def _spans_line(capsys, *argv):
    rc = spans.main(["--seed", str(2**31 + 5), "--cpu-rehearsal", str(SCALE),
                     *argv])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,loop", [("tgn-wiki.train", "train"),
                                           ("tgat-wiki.eval", "eval")])
def test_traced_rehearsal_reads_the_program_layers(capsys, workload, loop):
    line = _spans_line(capsys, "--workload", workload, "--seconds", "0.5",
                       "--trace", "1")
    assert line["device"]["platform"] == "cpu" and line["steps"] > 0
    got = line["readings"]
    # No TPU plane on the CPU, so no sampler programs to read.
    assert set(got) == {f"{m}.{loop}" for m in (
        "hooks_ms", "stage_ms", "loader_wait_ms", "compiles")}
    assert got[f"hooks_ms.{loop}"] > 0 and got[f"stage_ms.{loop}"] > 0
    assert 0 <= got[f"loader_wait_ms.{loop}"] <= line["host_wait_ms"]
    assert got[f"compiles.{loop}"] == 0 and line["compiled"] == []
    assert line["span_ms"]["hooks"] == pytest.approx(got[f"hooks_ms.{loop}"])
    assert {"hooks/PadBatchHook", "loader/stage", "loader/wait"} <= set(
        line["span_ms"])
    assert line["module_ms"] == {}


def test_untraced_windows_with_and_without_the_sink(capsys):
    line = _spans_line(capsys, "--workload", "tgn-wiki.train",
                       "--seconds", "0.3", "--trace", "0", "--sinks", "0,1")
    assert [w["sink"] for w in line["windows"]] == [0, 1]
    assert all(w["train_events_per_s"] > 0 for w in line["windows"])
