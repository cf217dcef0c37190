#!/usr/bin/env python3
"""Readings that set a cell's limits: the program against the reference
over many seeds (the lower readings), and the control and the faults over
a few (the upper readings).

    python3 benchmarks/chip/calibrate.py --workload tgat-wiki.train \
        --seeds 101 102 103 --control-seeds 101 102 103

Each seed is one line of JSON on standard output. For a train cell:
``program`` (the program's first steps against the reference), ``control``
(the reference computed in bfloat16, in the program's place) and
``half_batch`` (the reference with half of each batch left out and the
loss averaged over the rest, in the program's place). A state left
unchanged reads 1 by the change measure and needs no run. For an eval
cell: ``program`` and ``control`` score gaps over the batches a run
checks, after a window of ``--seconds``.

It runs the same set-up, mix and comparison as ``run.py``, in one process
for all seeds. ``--cpu-rehearsal SCALE`` rehearses on the CPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH_FILE = HERE.parents[1] / "BENCHMARK.json"


def worst_leaves(program: dict, ref: dict, n: int = 3) -> dict:
    """The leaves farthest from the reference, by each norm compared."""
    out = {}
    for key in ("grad", "change"):
        gaps = {k: abs(program[key][k] - v) / max(v, 1e-30)
                for k, v in ref[key].items()}
        top = sorted(gaps, key=gaps.get, reverse=True)[:n]
        out[key] = [[k, gaps[k], ref[key][k]] for k in top]
    return out


def readings(cell: dict, seed: int, control: bool, seconds: float,
             rehearsal=None) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from chip import harness, traffic, weights
    from chip.reference import check as ref_check

    config, mix_cfg = cell["config"], cell["traffic"]
    data, pipe, shapes = harness.build(config, seed, rehearsal)
    mix = traffic.MIXES[mix_cfg["loop"]](pipe, data, mix_cfg, config)
    mix.setup()
    if mix.loop == "eval":
        mix.window(seconds)
    mix.release()
    del pipe
    gc.collect()
    stream = ref_check.Stream(data.src, data.dst, data.edge_t,
                              data.edge_feats, config["sampler"]["k"])
    params = weights.make(seed, shapes)
    out = {"seed": seed}
    if mix.loop == "train":
        ref = ref_check.train(config["model"], config["optimizer"], stream,
                              mix.check_steps, params, jnp.float32,
                              num_nodes=data.num_nodes)
        out["program"] = ref_check.train_gaps(mix.program, ref)
        out["losses"] = {"program": mix.program["losses"],
                         "reference": ref["losses"]}
        out["worst_leaves"] = worst_leaves(mix.program, ref)
        if control:
            low = ref_check.train(config["model"], config["optimizer"],
                                  stream, mix.check_steps, params,
                                  jnp.bfloat16, num_nodes=data.num_nodes)
            out["control"] = ref_check.train_gaps(low, ref)
            half = [dict(s, mask=s["mask"] & (np.arange(s["mask"].size)
                                              < s["mask"].size // 2))
                    for s in mix.check_steps]
            cut = ref_check.train(config["model"], config["optimizer"],
                                  stream, half, params, jnp.float32,
                                  num_nodes=data.num_nodes)
            out["half_batch"] = ref_check.train_gaps(cut, ref)
        return out
    out["program"] = mix.check(stream, params, jnp.float32, seed)
    if control:
        out["control"] = mix.check(stream, params, jnp.float32, seed,
                                   stand_in=jnp.bfloat16)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--control-seeds", type=int, nargs="*", default=[])
    p.add_argument("--seconds", type=float, default=4.0,
                   help="eval cells: the window before the check")
    p.add_argument("--cpu-rehearsal", type=float, default=None)
    args = p.parse_args(argv)
    if str(HERE.parent) not in sys.path:
        sys.path.insert(0, str(HERE.parent))
    sys.path.insert(0, str(HERE.parents[1] / "src"))
    from chip import harness

    if args.cpu_rehearsal is None:
        from repro.utils.compile_cache import configure_compile_cache

        configure_compile_cache()
        import jax

        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(BENCH_FILE, args.workload)
    harness.check_device(cell["chips"], args.cpu_rehearsal)
    for seed in args.seeds:
        t0 = time.perf_counter()
        r = readings(cell, seed, seed in args.control_seeds, args.seconds,
                     args.cpu_rehearsal)
        r["seconds"] = time.perf_counter() - t0
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
