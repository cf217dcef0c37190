"""Each cell's mix runs end to end on the CPU at a tiny scale, with the
Pallas kernels in interpret mode, and prints the contract's result line.
A new configuration is found from its files alone."""

from __future__ import annotations

import json
import shutil
import types

import pytest

from chip import harness, run
from chip.rehearse import rehearse

KEYS = {"correct", "attempted", "failed", "metrics", "device", "check"}


def _check_line(line, bench_file, workload, trace):
    bench = json.loads(bench_file.read_text())
    want = set(KEYS) | ({"breakdown"} if trace else set())
    assert set(line) == want
    assert list(line)[-1] == "check"
    assert isinstance(line["correct"], bool)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    names = [m["name"] for m in bench["per_layer" if trace else "end_to_end"]
             if workload in m.get("workloads", [workload])]
    assert set(line["metrics"]) <= set(names)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    for c in line["check"].values():
        assert set(c) == {"value", "limit"}
    if trace:
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert {"busy_s", "window_s"} <= set(line["device"])
    else:
        assert set(line["metrics"]) == set(names)


@pytest.mark.parametrize("workload,trace", [
    ("tgat-wiki.train", 0), ("tgn-wiki.train", 1), ("tgat-wiki.eval", 0)])
def test_cell_rehearsal_prints_the_result_line(capsys, workload, trace):
    rc, line = rehearse(capsys, workload, trace)
    assert rc == 0
    _check_line(line, run.BENCH_FILE, workload, trace)


def test_a_new_configuration_is_found_from_its_files(capsys, tmp_path):
    """A configuration, its cell and its limits, all in new files; no
    file of the benchmark is edited."""
    chip = run.HERE
    bench = json.loads(run.BENCH_FILE.read_text())
    config = json.loads((chip / "configs" / "tgn-wiki.json").read_text())
    config.update(name="tgn-wiki-k5", sampler=dict(config["sampler"], k=5))
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "tgn-wiki-k5.json").write_text(json.dumps(config))
    shutil.copytree(chip / "traffic", tmp_path / "traffic")
    (tmp_path / "limits").mkdir()
    shutil.copy(chip / "limits" / "tgn-wiki.train.json",
                tmp_path / "limits" / "tgn-wiki-k5.train.json")
    bench["configs"].append({"name": "tgn-wiki-k5", "source": "test",
                             "file": "configs/tgn-wiki-k5.json",
                             "reduced": [], "why": "test"})
    bench["workloads"] = [{"name": "tgn-wiki-k5.train", "config": "tgn-wiki-k5",
                           "traffic": "train", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "tgn-wiki.train" in m["workloads"]:
            m["workloads"].append("tgn-wiki-k5.train")
    bench_file = tmp_path / "BENCHMARK.json"
    bench_file.write_text(json.dumps(bench))

    rc, line = rehearse(capsys, "tgn-wiki-k5.train", bench_file=bench_file)
    assert rc == 0
    _check_line(line, bench_file, "tgn-wiki-k5.train", 0)


@pytest.mark.parametrize("strict", [False, True])
def test_a_declared_metric_that_reads_nothing_refuses_a_chip_run(strict):
    """On the chip a per-layer metric that names the cell has to be read;
    a rehearsal, which has no device trace or peak, leaves it out."""
    cell = harness.load_cell(run.BENCH_FILE, "tgat-wiki.train")
    empty = types.SimpleNamespace(loop="train", reduced=None, peak=None,
                                  work=[], records={"waits": [0.002]})
    if strict:
        with pytest.raises(harness.Refused, match="read nothing"):
            harness.per_layer(cell, empty, strict=True)
    else:
        assert set(harness.per_layer(cell, empty, strict=False)) == {
            "host_wait_ms.train"}
