"""Nothing compiles inside the measured window: set-up warms every shape
the window uses, the split's last, short batch and the first step of a
new pass included."""

from __future__ import annotations

import jax
import pytest

from chip import harness, run, traffic
from chip.rehearse import SCALE

COMPILE = "/jax/core/compile/backend_compile_duration"


def window_compiles(workload: str, skip=()) -> int:
    """Compiles in windows that cross the first pass end of ``workload``'s
    mix, started 2 batches into the split; ``skip`` names set-up warm-ups
    (``TrainMix`` methods) left out."""
    cell = harness.load_cell(run.BENCH_FILE, workload)
    mix_cfg = dict(cell["traffic"], start_batch=2)
    data, pipe, _ = harness.build(cell["config"], 2**31 + 17, SCALE)
    mix = traffic.MIXES[mix_cfg["loop"]](pipe, data, mix_cfg, cell["config"])
    for name in skip:
        setattr(mix, name, lambda *a: None)
    mix.setup()
    per_pass = -(-len(pipe.train_data.src) // pipe.batch_size)
    compiles = []

    def listen(event, secs, **kw):
        if event == COMPILE:
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        steps = 0
        while steps < per_pass:
            steps += mix.window(0.5)["steps"]
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
        mix.release()
    return len(compiles)


@pytest.mark.parametrize("workload", ["tgn-wiki.train", "tgat-wiki.train"])
def test_nothing_compiles_in_the_window(workload):
    assert window_compiles(workload) == 0
