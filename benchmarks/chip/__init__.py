"""Chip benchmark of the temporal-graph training and evaluation paths.

Run one cell of ``BENCHMARK.json`` (at the repository root) on the chip:

    python3 benchmarks/chip/run.py --workload tgat-wiki.train --seed 7 \
        --seconds 30 --trace 0

Everything that defines the measurement lives in this package: the cell
configurations (``configs/``), the traffic mixes (``traffic/``), the
per-layer metric readers (``metrics/``), the work counts behind every
roofline and utilization share (``roofline/``), the peak table
(``peaks.json``), the trace reduction (``tracing.py``), the plain
reference that decides ``correct`` (``reference/``) and its limits
(``limits/``). The program under test (``src/repro``) supplies only the
system, its batches and its kernel names.
"""
