"""The control, the plain reference computed one precision down
(bfloat16) in the program's place, fails the cell's limits."""

from __future__ import annotations

import pytest

from chip import calibrate, harness, run
from chip.rehearse import SCALE


@pytest.mark.parametrize("workload", [
    "tgn-wiki.train", "tgat-wiki.train", "tgat-wiki.eval"])
def test_control_is_not_correct(workload):
    cell = harness.load_cell(run.BENCH_FILE, workload)
    r = calibrate.readings(cell, 2**31 + 11, control=True, seconds=0.5,
                           rehearsal=SCALE)
    limits = cell["limits"]
    assert any(r["control"][k] > limits[k] for k in limits), r["control"]
    if "half_batch" in r:
        assert any(r["half_batch"][k] > limits[k] for k in limits)
