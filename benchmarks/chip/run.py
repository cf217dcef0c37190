#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload tgat-wiki.train --seed 7 \
        --seconds 30 --trace 0

One process, one run: set-up (the stream and weights from ``--seed``, the
pipeline, compile or cache load, warm-up), a window of ``--seconds``, the
check of what the window produced against the plain reference, and one
JSON line as the last line of standard output. ``--trace 1`` records a
profiler trace of the window and reports the cell's per-layer metrics in
place of its end-to-end ones.

The run refuses a device that is not in ``peaks.json`` and fewer chips
than the cell asks for: it exits non-zero and prints no result.
``--cpu-rehearsal SCALE`` runs the same path on the CPU, on a stream cut to
``SCALE`` with batches of 20 events and the Pallas kernels in interpret
mode, to rehearse a cell without the chip; its numbers are not metrics.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
BENCH_FILE = HERE.parents[1] / "BENCHMARK.json"


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True, help="cell name")
    p.add_argument("--seed", type=int, required=True,
                   help="seed of the stream, the weights and the traffic")
    p.add_argument("--seconds", type=float, required=True,
                   help="length of the measured window")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0,
                   help="1: trace the window, report per-layer metrics")
    p.add_argument("--cpu-rehearsal", type=float, default=None,
                   metavar="SCALE",
                   help="rehearse on the CPU at this stream scale")
    return p.parse_args(argv)


def main(argv=None, bench_file: Path = BENCH_FILE, fault=None) -> int:
    args = parse(argv)
    if str(HERE.parent) not in sys.path:
        sys.path.insert(0, str(HERE.parent))
    from chip import harness

    return harness.run_cell(bench_file, args, T_START, fault)


if __name__ == "__main__":
    raise SystemExit(main())
