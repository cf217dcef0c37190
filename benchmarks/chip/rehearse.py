"""Run a cell in-process on the CPU, as the tests rehearse it."""

from __future__ import annotations

import json

from chip import run

SCALE = 0.005


def rehearse(capsys, workload: str, trace: int = 0, seed: int = 2**31 + 5,
             bench_file=run.BENCH_FILE, fault=None):
    """Exit code and parsed last stdout line of one rehearsal run."""
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "0.5", "--trace", str(trace),
                   "--cpu-rehearsal", str(SCALE)], bench_file=bench_file,
                  fault=fault)
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(lines[-1])
