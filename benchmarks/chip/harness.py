"""One run of one cell: find it, check the device, build the system, drive
its mix, reduce, check the output, print the result line.

Everything particular to a configuration, a mix or a per-layer metric is
read from files found by name: the cell's entry in ``BENCHMARK.json``
names its configuration file and its mix; beside ``configs/`` are
``traffic/<mix>.json`` and ``limits/<cell>.json``; each per-layer metric
is read by ``metrics/<metric>.py``.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path

HERE = Path(__file__).resolve().parent
REHEARSAL_BATCH = 20


class Refused(Exception):
    """This run cannot be measured here: exit non-zero, print no result."""


def load_cell(bench_file, name: str) -> dict:
    """The cell ``name`` of ``bench_file`` with everything it names."""
    bench_file = Path(bench_file)
    bench = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in {bench_file}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config_file = bench_file.resolve().parent / entry["file"]
    root = config_file.parent.parent

    def applies(metric):
        return name in metric.get("workloads", [name])

    return {
        "name": name,
        "chips": cell["chips"],
        "config": json.loads(config_file.read_text()),
        "traffic": json.loads(
            (root / "traffic" / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads((root / "limits" / f"{name}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def check_device(chips: int, rehearsal):
    """The device record of the result line and the chip's peaks; refuses
    a device that is not in ``peaks.json`` or too few chips."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}
    if rehearsal is not None:
        if d0.platform != "cpu":
            raise Refused("--cpu-rehearsal runs on the CPU only")
        return info, None
    if d0.platform == "cpu":
        raise Refused("no accelerator: JAX found only the CPU")
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
    if d0.device_kind not in peaks:
        raise Refused(f"device kind {d0.device_kind!r} is not in peaks.json")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return info, peaks[d0.device_kind]


def build(config: dict, seed: int, rehearsal):
    """The stream and the compiled pipeline of ``config``, with the
    benchmark's weights from ``seed`` installed."""
    from repro.data import generate
    from repro.tg import DataSpec, Experiment, ModelSpec, SamplerSpec, TrainSpec

    from chip import weights

    data_kw = dict(config["data"])
    train_kw = dict(config["train"], seed=seed & 0xFFFFFFFF)
    if rehearsal is not None:
        data_kw["scale"] = rehearsal
        train_kw["batch_size"] = REHEARSAL_BATCH
    data = generate(data_kw["dataset"], scale=data_kw["scale"], seed=seed)
    pipe = Experiment(
        data=DataSpec(**data_kw), model=ModelSpec(**config["model"]),
        sampler=SamplerSpec(**config["sampler"]),
        train=TrainSpec(**train_kw),
    ).compile(data)
    if rehearsal is not None:
        # The fused Pallas kernels, through the interpreter.
        pipe.fused = "interpret"
        pipe._build_steps()
    opt = config["optimizer"]
    got = {k: getattr(pipe.opt_cfg, k) for k in opt if k != "name"}
    if got != {k: v for k, v in opt.items() if k != "name"}:
        raise RuntimeError(f"the program's optimizer {got} is not {opt}")
    shapes = weights.layout(config["model"], data.num_nodes,
                            data.edge_feat_dim)
    differs = weights.same_layout(pipe.params, shapes)
    if differs:
        raise RuntimeError(differs)
    pipe.params = weights.make(seed, shapes)
    return data, pipe, shapes


def read_metric(name: str, run) -> float | None:
    spec = importlib.util.spec_from_file_location(
        f"chip_metric_{name.replace('.', '_')}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def per_layer(cell: dict, run, strict: bool) -> dict:
    """The cell's per-layer metrics read from ``run``. A metric that reads
    nothing is left out, unless it names this cell in its ``workloads``
    and ``strict`` (a run on the chip): that refuses the run."""
    out = {}
    for m in cell["per_layer"]:
        value = read_metric(m["name"], run)
        if value is None:
            if strict and cell["name"] in m.get("workloads", ()):
                raise Refused(f"per-layer metric {m['name']} read nothing "
                              f"in {cell['name']}")
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def _peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _work(cell, mix, data) -> list:
    from chip.roofline import steps

    model = cell["config"]["model"]
    return [steps.step_work(model["name"], model["kwargs"],
                            cell["config"]["sampler"]["k"],
                            data.edge_feat_dim, data.num_nodes, arrays,
                            train=mix.loop == "train")
            for arrays in mix.flush_kept()]


def run_cell(bench_file, args, t_start: float, fault=None) -> int:
    """One run; ``fault`` (tests only) is called with the built pipeline,
    to break the timed path underneath the harness."""
    try:
        cell = load_cell(bench_file, args.workload)
        sys.path.insert(0, str(HERE.parents[1] / "src"))
        if args.cpu_rehearsal is None:
            from repro.utils.compile_cache import configure_compile_cache

            configure_compile_cache()
            import jax

            # Every program, however quick to compile, is cached, so that
            # only a cell's first run in a checkout compiles.
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        device, peak = check_device(cell["chips"], args.cpu_rehearsal)
    except (Refused, ImportError, OSError, KeyError) as e:
        print(f"refused: {e}", file=sys.stderr, flush=True)
        return 2
    import jax
    import jax.numpy as jnp

    from chip import tracing, traffic, weights
    from chip.reference import check as ref_check
    from chip.roofline import steps

    config, mix_cfg = cell["config"], cell["traffic"]
    data, pipe, shapes = build(config, args.seed, args.cpu_rehearsal)
    if fault is not None:
        fault(pipe)
    keep = steps.model(config["model"]["name"]).KEYS if args.trace else ()
    mix = traffic.MIXES[mix_cfg["loop"]](pipe, data, mix_cfg, config, keep)
    mix.setup()
    setup_s = time.perf_counter() - t_start

    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="chip-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        records = mix.window(args.seconds)
    if trace_dir:
        jax.profiler.stop_trace()
    device["memory_peak_bytes"] = max(_peak_bytes(d) for d in jax.devices())

    result = {"correct": False, "attempted": mix.attempted(), "failed": 0,
              "metrics": {}, "device": device}
    if args.trace:
        reduced = tracing.reduce(tracing.newest_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        run = types.SimpleNamespace(
            loop=mix.loop, records=records, reduced=reduced, peak=peak,
            work=_work(cell, mix, data))
        try:
            result["metrics"] = per_layer(
                cell, run, strict=args.cpu_rehearsal is None)
        except Refused as e:
            print(f"refused: {e}", file=sys.stderr, flush=True)
            return 3
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        top = sorted(reduced.op_seconds.items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {"device_ops": [list(x) for x in top],
                               "idle_gaps": [list(x) for x in reduced.gaps]}
    else:
        values = dict(mix.end_to_end(), setup_s=setup_s)
        for m in cell["end_to_end"]:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}

    # The reference runs with the program's state freed.
    mix.release()
    del pipe
    gc.collect()
    stream = ref_check.Stream(data.src, data.dst, data.edge_t,
                              data.edge_feats, config["sampler"]["k"])
    params = weights.make(args.seed, shapes)
    readings = mix.check(stream, params, jnp.float32, args.seed)
    limits = cell["limits"]
    checks = {k: (readings[k], limits[k]) for k in limits}
    result["correct"] = all(v <= lim for v, lim in checks.values())
    for k, (v, lim) in checks.items():
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr)
    result["check"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in checks.items()}
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0

