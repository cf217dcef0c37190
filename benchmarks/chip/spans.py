#!/usr/bin/env python3
"""Run one benchmark cell with the program's own telemetry on, and print
what the program was doing in the window.

    python3 benchmarks/chip/spans.py --workload tgn-wiki.train --seed 7 \
        --seconds 30 --trace 1

Set-up is the benchmark's (``harness.build``, the mix's ``setup``). With
``--trace 1`` one window runs with a ``MemorySink`` on the pipeline's
telemetry under the profiler, and the JSON line gives the program's
per-step readings (``program.readings``: hooks, staging, prefetch wait,
sampler programs, compiles), every program span and the busiest device
programs per step, the idle gaps labelled with program spans, and the
host wait the benchmark reads. With ``--trace 0`` the windows of
``--sinks`` (``0,1,0,1``: 1 attaches the sink) run back to back in one
process, untraced, and the line gives each window's end-to-end numbers:
what the telemetry costs when it is on. No output is checked against the
reference; ``run.py`` does that. ``--cpu-rehearsal SCALE`` as in
``run.py``.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH_FILE = HERE.parents[1] / "BENCHMARK.json"


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True,
                   help="length of each window")
    p.add_argument("--trace", type=int, choices=(0, 1), default=1)
    p.add_argument("--sinks", default="0,1,0,1",
                   help="untraced windows, 1 where the sink is attached")
    p.add_argument("--cpu-rehearsal", type=float, default=None,
                   metavar="SCALE")
    return p.parse_args(argv)


def traced_window(mix, tel, seconds: float) -> dict:
    import jax

    from chip import program, tracing
    from repro.obs import MemorySink

    trace_dir = tempfile.mkdtemp(prefix="chip-spans-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    sink = tel.attach(MemorySink())
    tel.reset()
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tracing.WINDOW):
            records = mix.window(seconds)
    finally:
        jax.profiler.stop_trace()
        tel.flush()
        tel.detach(sink)
    try:
        path = tracing.newest_xplane(trace_dir)
        bench_spans, devices = tracing.load(path)
        prog = program.load(path)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    compiles = sum(r["value"] for r in sink.records
                   if r["kind"] == "counter" and r["name"] == "jax/compiles")
    waits, steps = records["waits"], records["steps"]
    w0, w1 = program.window(bench_spans)
    modules = program.module_seconds(prog, w0, w1)
    return {
        "steps": steps,
        "readings": program.readings(mix.loop, prog, bench_spans, steps,
                                     compiles),
        "host_wait_ms": 1e3 * sum(waits) / len(waits),
        # Per step: every program span, and the device's busiest programs.
        "span_ms": {name: 1e3 * s / steps for name, s in
                    sorted(program.span_seconds(prog, w0, w1).items())},
        "module_ms": {name: 1e3 * s / steps for name, s in
                      sorted(modules.items(), key=lambda kv: -kv[1])[:12]},
        "compiled": sorted({r["attrs"]["fun"] for r in sink.records
                            if r["name"] == "jax/compile"}),
        "idle_gaps": program.label_gaps(bench_spans, devices, prog),
    }


def main(argv=None) -> int:
    args = parse(argv)
    for path in (str(HERE.parent), str(HERE.parents[1] / "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    from chip import harness, traffic

    cell = harness.load_cell(BENCH_FILE, args.workload)
    if args.cpu_rehearsal is None:
        import jax

        from repro.utils.compile_cache import configure_compile_cache

        configure_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        device, _ = harness.check_device(cell["chips"], args.cpu_rehearsal)
    except harness.Refused as e:
        print(f"refused: {e}", file=sys.stderr, flush=True)
        return 2
    data, pipe, _ = harness.build(cell["config"], args.seed,
                                  args.cpu_rehearsal)
    mix_cfg = cell["traffic"]
    mix = traffic.MIXES[mix_cfg["loop"]](pipe, data, mix_cfg, cell["config"])
    mix.setup()
    tel = pipe.telemetry
    out = {"workload": args.workload, "seed": args.seed, "device": device}
    if args.trace:
        out.update(traced_window(mix, tel, args.seconds))
    else:
        from repro.obs import MemorySink

        out["windows"] = []
        for flag in args.sinks.split(","):
            sink = tel.attach(MemorySink()) if flag == "1" else None
            mix.window(args.seconds)
            if sink is not None:
                tel.detach(sink)
            out["windows"].append(dict(mix.end_to_end(), sink=int(flag)))
    mix.release()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
