#!/usr/bin/env python3
"""Chip smoke test: drive the main TGAT link-training path once on a TPU.

    python chip_smoke.py                # one chip (the default check)
    python chip_smoke.py --four-chips   # 2x2 mesh against one chip
    JAX_PLATFORMS=cpu python chip_smoke.py --allow-cpu-rehearsal --scale 0.01
                                        # rehearsal on the CPU, tiny stream

One chip, two phases, each through ``tg.Experiment``:

1. TGAT at its published widths (2 layers, d_model 100, d_time 100, 2
   heads, K=20) on the full wikipedia-shaped stream (157,474 events,
   172-dim edge features, generated from ``--seed``), device recency
   sampler, batch 200, 20 eval negatives. Compile; count
   ``tpu_custom_call`` in the compiled train step (the fused Pallas
   kernels must be staged there); check the kernel's loss and gradients
   on a warm train batch (batch 50: the recency buffer holds ~10k events)
   against the materializing jnp oracle (and its float64 answer, where f32
   is the limit); one training epoch; one ``evaluate("val")``.
2. GCN over hourly snapshots of the same stream: one scanned epoch, which
   runs the ``segment_sum`` Pallas kernel.

``--four-chips`` runs only the TGAT experiment on a 2x2 ``(data, nodes)``
mesh (``data_shards=2``, ``shards=2``) for its first 36 train steps and
the same steps on one chip, each step from the same parameters and
optimizer state, and checks each step's loss, its gradient leaf by leaf,
and that the mesh's update is AdamW applied to that gradient.

A failed check raises, so the script exits non-zero and prints no result.
On success the last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Times printed here are a smoke check of one run, not a benchmark.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Kernel-vs-oracle bound: the gradient tolerance of the kernel parity
# harness (tests/kernels/harness.py, GRAD_TOL).
KERNEL_TOL = dict(rtol=1e-4, atol=1e-4)
# 2x2 mesh vs one chip: the loss bound of tests/test_distributed.py, over
# the first MESH_STEPS train steps ("a few dozen").
MESH_LOSS_TOL = 1e-4
MESH_STEPS = 36
# The mesh's updated parameters vs AdamW applied to the mesh's own gradient
# on one chip: the same arithmetic, so only rounding separates them.
UPDATE_TOL = 1e-6


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    """Fail the smoke run (non-zero exit, no result line) unless ``ok``."""
    if not ok:
        raise RuntimeError(f"chip smoke check failed: {msg}")


def tgat_experiment(scale: float, seed: int):
    from repro.tg import DataSpec, Experiment, ModelSpec, SamplerSpec, TrainSpec

    return Experiment(
        data=DataSpec("wikipedia", scale=scale),
        model=ModelSpec("tgat"),
        sampler=SamplerSpec(kind="recency", k=20, device=True),
        train=TrainSpec(batch_size=200, eval_negatives=20, seed=seed),
    )


def span_attrs(sink, name: str) -> dict:
    """Attributes of the last ``name`` span recorded in ``sink``."""
    recs = [r for r in sink.records
            if r.get("kind") == "span" and r["name"] == name]
    check(bool(recs), f"no {name!r} span was recorded")
    return recs[-1]["attrs"]


def train_batch(pipe, index: int):
    """Staged train batch ``index`` of a fresh epoch (or the last one, if
    the epoch is shorter)."""
    pipe.reset_epoch_state()
    with contextlib.closing(pipe.train_batches()) as batches:
        for i, bt in enumerate(batches):
            if i == index:
                break
    return bt


def within(got, want, rtol: float = KERNEL_TOL["rtol"],
           atol: float = KERNEL_TOL["atol"]):
    """Elementwise ``|got - want| <= atol + rtol |want|``, as
    ``np.testing.assert_allclose`` (and so the kernel harness) tests it."""
    import numpy as np

    return np.abs(got - want) <= atol + rtol * np.abs(want)


_GRAD_FNS: dict = {}


def tgat_loss_and_grad(pipe, fused: str, params, batch):
    """Loss and parameter gradients of one staged batch through TGAT with
    the given ``fused`` mode ("kernel", "interpret", or the "ref" oracle)."""
    import jax

    from repro.models.tg import tgat
    from repro.models.tg.common import bce_link_loss

    key = (id(pipe), fused)
    if key not in _GRAD_FNS:
        def loss(params, batch):
            pos, neg = tgat.link_scores(params, pipe.cfg, batch,
                                        pipe.batch_size, fused=fused)
            return bce_link_loss(pos, neg, batch["batch_mask"])

        _GRAD_FNS[key] = jax.jit(jax.value_and_grad(loss))
    return _GRAD_FNS[key](params, batch)


def f64_loss_and_grad(pipe, params, batch):
    """Loss and gradients of ``batch`` through the jnp oracle in float64 on
    the host CPU: the answer f32 rounding is measured against."""
    import jax
    import numpy as np

    cpu = jax.devices("cpu")[0]

    def f64_on_cpu(tree):
        def cast(x):
            x = np.asarray(x)
            return jax.device_put(
                x.astype(np.float64) if x.dtype == np.float32 else x, cpu)
        return jax.tree.map(cast, jax.device_get(tree))

    with jax.enable_x64(True):
        loss, grads = tgat_loss_and_grad(pipe, "ref", f64_on_cpu(params),
                                         f64_on_cpu(batch))
        return float(loss), jax.tree.map(np.asarray, grads)


def check_grads(label: str, got, want, truth):
    """Check gradient pytree ``got`` against ``want`` leaf by leaf, or fail
    naming the leaves at fault. Returns the largest difference, the leaf
    it is in, and one note per leaf with elements outside the bound.

    Every element must be within the kernel harness's bound of ``want``.
    The one exception is a leaf where f32 cannot resolve the gradient at
    all: one where ``want`` itself is outside that bound of the float64
    answer (``truth()``, computed only if some leaf needs it). There
    ``got`` passes if its worst error against float64 is no larger than
    ``want``'s, plus the bound's ``atol``. TGAT's ``time.w`` is such a
    leaf at these timestamps: the Bochner phase ``dt * w + b`` reaches
    ~1e6 rad, where one f32 ulp is ~0.06 rad, so two f32 programs that
    round or reduce the phase differently disagree on ``cos`` in the
    second decimal, and the gradient of ``w`` multiplies that by dt.
    """
    import jax
    import numpy as np
    from jax.tree_util import keystr, tree_flatten_with_path

    leaves = [(keystr(path), np.asarray(g, np.float64),
               np.asarray(w, np.float64))
              for (path, w), g in zip(tree_flatten_with_path(want)[0],
                                      jax.tree.leaves(got))]
    diffs = [float(np.abs(g - w).max(initial=0.0)) for _, g, w in leaves]
    worst = int(np.argmax(diffs))
    outside = [(i, int((~within(g, w)).sum()))
               for i, (_, g, w) in enumerate(leaves)]
    outside = [(i, n) for i, n in outside if n]
    notes, failed = [], []
    if outside:
        t_leaves = jax.tree.leaves(truth())
        for i, n in outside:
            name, g, w = leaves[i]
            t = np.asarray(t_leaves[i], np.float64)
            f32_limited = not within(w, t).all()
            err_g = float(np.abs(g - t).max())
            err_w = float(np.abs(w - t).max())
            notes.append(
                f"{name}: {n} of {g.size} elements, max |{label}-f64| "
                f"{err_g!r}, max |ref-f64| {err_w!r}, ref "
                f"{'outside' if f32_limited else 'inside'} the bound of f64")
            if not f32_limited or err_g > err_w + KERNEL_TOL["atol"]:
                failed.append(name)
    check(not failed, f"{label} gradients outside the bound of the "
          f"reference in {', '.join(failed)}: {'; '.join(notes)}")
    return diffs[worst], leaves[worst][0], notes


def kernel_vs_ref(pipe, index: int, kernel_mode: str) -> None:
    """Loss and gradients of train batch ``index`` through the fused kernel
    (``fused=kernel_mode``) and through the materializing jnp oracle
    (``fused="ref"``), at full f32 matmul precision on both sides so the
    comparison sees the kernel and nothing else. The loss must be within
    the kernel harness's bound of the oracle, and the gradients must pass
    ``check_grads``."""
    import jax
    import numpy as np

    bt = train_batch(pipe, index)
    n_nbr = int(np.asarray(bt["nbr_mask"]).sum())
    check(n_nbr > 0, f"train batch {index} has no sampled neighbor")

    with jax.default_matmul_precision("highest"):
        lk, gk = tgat_loss_and_grad(pipe, kernel_mode, pipe.params, bt)
        lr, gr = tgat_loss_and_grad(pipe, "ref", pipe.params, bt)
        lt, gt = f64_loss_and_grad(pipe, pipe.params, bt)
    lk, lr = float(lk), float(lr)
    log(f"[tgat] kernel vs ref on train batch {index} ({n_nbr} valid hop-1 "
        f"neighbor slots): loss {lk!r} vs {lr!r} (|diff| {abs(lk - lr)!r}; "
        f"float64 oracle {lt!r}); bound rtol={KERNEL_TOL['rtol']} "
        f"atol={KERNEL_TOL['atol']}")
    check(math.isfinite(lk) and math.isfinite(lr), "non-finite loss")
    check(bool(within(np.float64(lk), np.float64(lr))),
          f"kernel loss off the oracle by {abs(lk - lr)!r}")
    dmax, leaf, notes = check_grads("kernel", gk, gr, lambda: gt)
    log(f"[tgat] grads: max |kernel - ref| {dmax!r} in {leaf}; leaves "
        f"outside the bound: {'; '.join(notes) or 'none'}")


def tgat_phase(args, on_tpu: bool) -> None:
    import jax
    import numpy as np

    from repro.obs import MemorySink, Telemetry

    sink = MemorySink()
    t0 = time.perf_counter()
    pipe = tgat_experiment(args.scale, args.seed).compile(
        telemetry=Telemetry(sink))
    cfg, data = pipe.cfg, pipe.data
    log(f"[tgat] compile (stream + pipeline) {time.perf_counter() - t0!r} s:"
        f" N={data.num_nodes} E={len(data.src)} d_edge={data.edge_feat_dim}"
        f" layers={cfg.num_layers} d_model={cfg.d_model}"
        f" d_time={cfg.d_time} heads={cfg.num_heads} k={cfg.k}")

    bt = train_batch(pipe, 0)
    check("nbr_buf" in bt, "the device recency hook exposed no nbr_buf")
    t0 = time.perf_counter()
    hlo = pipe.train_step.lower(pipe.params, pipe.opt_state, bt).compile(
        ).as_text()
    n_custom = hlo.count("tpu_custom_call")
    log(f"[tgat] train step compiled in {time.perf_counter() - t0!r} s; "
        f"tpu_custom_call count {n_custom}")
    if on_tpu:
        check(n_custom > 0, "no Pallas kernel in the compiled train step")

    t0 = time.perf_counter()
    # A batch deep enough into the epoch that the recency buffer is warm
    # (batch 0 sees an empty buffer: every neighborhood is masked out).
    kernel_vs_ref(pipe, 50, "kernel" if on_tpu else "interpret")
    log(f"[tgat] kernel vs ref check {time.perf_counter() - t0!r} s")

    t0 = time.perf_counter()
    loss, secs = pipe.train_epoch()
    steps = span_attrs(sink, "ctdg/epoch")["steps"]
    log(f"[tgat] train epoch: {steps} steps, mean loss {loss!r}, "
        f"epoch {secs!r} s (call {time.perf_counter() - t0!r} s)")
    check(math.isfinite(loss), f"non-finite epoch loss {loss!r}")
    check(steps > 0, "the train epoch ran no step")

    t0 = time.perf_counter()
    val_mrr, secs = pipe.evaluate("val")
    n_eval = sum(1 for r in sink.records if r.get("kind") == "span"
                 and r["name"] == "ctdg/eval_step")
    log(f"[tgat] evaluate(val): MRR {val_mrr!r} over {n_eval} batches, "
        f"val loop {secs!r} s (call incl. warm-up "
        f"{time.perf_counter() - t0!r} s)")
    check(0.0 <= val_mrr <= 1.0, f"val MRR {val_mrr!r} outside [0, 1]")
    check(bool(np.isfinite(np.asarray(jax.tree.leaves(pipe.params)[0])).all()),
          "non-finite parameters after the epoch")


def gcn_phase(args, on_tpu: bool) -> None:
    from repro.obs import MemorySink, Telemetry
    from repro.tg import DataSpec, Experiment, ModelSpec, TrainSpec

    sink = MemorySink()
    t0 = time.perf_counter()
    pipe = Experiment(
        data=DataSpec("wikipedia", scale=args.scale, discretization="h"),
        model=ModelSpec("gcn"),
        train=TrainSpec(seed=args.seed),
    ).compile(telemetry=Telemetry(sink))
    snaps = pipe.snapshots
    log(f"[gcn] compile (stream + snapshots) {time.perf_counter() - t0!r} s:"
        f" {snaps.num_snapshots} hourly snapshots, edge capacity "
        f"{snaps.capacity}, N={pipe.data.num_nodes}")

    t0 = time.perf_counter()
    loss, secs = pipe.train_epoch()
    pairs = span_attrs(sink, "dtdg/epoch")["pairs"]
    log(f"[gcn] scanned epoch: {pairs} snapshot pairs, mean loss {loss!r}, "
        f"epoch {secs!r} s (call {time.perf_counter() - t0!r} s)")
    check(math.isfinite(loss), f"non-finite GCN epoch loss {loss!r}")
    check(pairs > 0, "the GCN epoch ran no snapshot pair")

    hlo = pipe.lower_train_chunk().compile().as_text()
    n_custom = hlo.count("tpu_custom_call")
    log(f"[gcn] tpu_custom_call count in the scanned epoch {n_custom}")
    if on_tpu:
        check(n_custom > 0, "no segment_sum kernel in the GCN epoch")


def four_chip_phase(args, on_tpu: bool) -> None:
    import jax
    import numpy as np

    from repro.data import generate
    from repro.optim import adamw_init, adamw_update

    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 devices, have "
          f"{len(devices)}")
    single = tgat_experiment(args.scale, args.seed)
    meshed = dataclasses.replace(
        single,
        sampler=dataclasses.replace(single.sampler, shards=2),
        train=dataclasses.replace(single.train, data_shards=2),
    )
    t0 = time.perf_counter()
    data = generate("wikipedia", scale=args.scale)
    p_mesh = meshed.compile(data)
    p_one = single.compile(data)
    log(f"[mesh] compile (stream + two pipelines) "
        f"{time.perf_counter() - t0!r} s; data_shards "
        f"{meshed.train.data_shards}, node shards {meshed.sampler.shards}")

    # Each step starts both pipelines from the one-chip run's parameters
    # and optimizer state: a free-running pair drifts apart within a few
    # steps whatever the mesh does, because the mesh sums the gradient in
    # another order and TGAT's time-frequency gradient amplifies that f32
    # rounding (see ``check_grads``); the drift then compounds.
    def on_mesh(tree, like):
        return jax.tree.map(lambda x, y: jax.device_put(x, y.sharding),
                            tree, like)

    # The gradient each train step applies, read through the step itself:
    # from zeroed moments, AdamW's first moment comes out as (1 - b1) * g.
    # On the mesh this is the data-axis gradient psum and the node-axis
    # psum in the sharded fused layer's VJP.
    cfg = p_one.opt_cfg
    zero_one = adamw_init(p_one.params)
    zero_mesh = on_mesh(zero_one, p_mesh.opt_state)

    def step_grads(pipe, params, zero, batch):
        _, opt, loss = pipe.train_step(params, zero, batch)
        return float(loss), jax.tree.map(
            lambda mu: np.asarray(mu, np.float64) / (1.0 - cfg.b1), opt["mu"])

    adamw = jax.jit(lambda p, g, o: adamw_update(p, g, o, cfg)[0])

    l_one, l_mesh, gdiff, udiff, pdiff = [], [], (0.0, ""), 0.0, (0.0, "")
    t_one = t_mesh = 0.0
    p_one.reset_epoch_state()
    p_mesh.reset_epoch_state()
    with contextlib.closing(p_one.train_batches()) as b_one, \
            contextlib.closing(p_mesh.train_batches()) as b_mesh:
        for step, x_one, x_mesh in zip(range(MESH_STEPS), b_one, b_mesh):
            params, opt = p_one.params, p_one.opt_state
            params_m = on_mesh(params, p_mesh.params)
            t0 = time.perf_counter()
            loss, g_mesh = step_grads(p_mesh, params_m, zero_mesh, x_mesh)
            new_mesh, _, _ = p_mesh.train_step(
                params_m, on_mesh(opt, p_mesh.opt_state), x_mesh)
            l_mesh.append(loss)
            t_mesh += time.perf_counter() - t0
            t0 = time.perf_counter()
            loss, g_one = step_grads(p_one, params, zero_one, x_one)
            p_one.params, p_one.opt_state, _ = p_one.train_step(
                params, opt, x_one)
            l_one.append(loss)
            t_one += time.perf_counter() - t0

            # The mesh's gradient, leaf by leaf, against the one chip's.
            dmax, leaf, notes = check_grads(
                "2x2", g_mesh, g_one,
                lambda: f64_loss_and_grad(p_one, params, x_one)[1])
            if notes:
                log(f"[mesh] step {step} grads outside the bound: "
                    f"{'; '.join(notes)}")
            if dmax >= gdiff[0]:
                gdiff = (dmax, f"{leaf} at step {step}")
            # The mesh's update: AdamW applied to its own gradient from
            # the shared state, to rounding.
            want = adamw(params, jax.tree.map(np.float32, g_mesh), opt)
            for (path, w), got in zip(
                    jax.tree_util.tree_flatten_with_path(want)[0],
                    jax.tree.leaves(new_mesh)):
                got, w = np.asarray(got), np.asarray(w)
                udiff = max(udiff, float(np.abs(got - w).max()))
                check(bool(within(got, w, UPDATE_TOL, UPDATE_TOL).all()),
                      f"2x2 update of {jax.tree_util.keystr(path)} at step "
                      f"{step} is not AdamW of its gradient")
            # Against the one chip's update it is read, not bounded: AdamW
            # divides each element's step by its own RMS gradient, so where
            # a gradient is ~0 a rounding-level difference in it moves the
            # update by up to ~lr.
            for (path, a), b, g in zip(
                    jax.tree_util.tree_flatten_with_path(p_one.params)[0],
                    jax.tree.leaves(new_mesh), jax.tree.leaves(g_one)):
                d = np.abs(np.asarray(a) - np.asarray(b))
                at = np.unravel_index(int(np.argmax(d)), d.shape)
                if d[at] > pdiff[0]:
                    pdiff = (float(d[at]), f"{jax.tree_util.keystr(path)}"
                             f"{list(map(int, at))} at step {step}, one-chip"
                             f" grad there {float(g[at])!r}")
    diff = float(np.max(np.abs(np.asarray(l_mesh) - np.asarray(l_one))))
    log(f"[mesh] {len(l_one)} steps: 2x2 mesh {t_mesh!r} s, one chip "
        f"{t_one!r} s (compile included)")
    log(f"[mesh] losses 2x2 first/last {l_mesh[0]!r} / {l_mesh[-1]!r}; "
        f"one chip {l_one[0]!r} / {l_one[-1]!r}; max |diff| {diff!r} "
        f"(bound {MESH_LOSS_TOL})")
    log(f"[mesh] grads within the bound (rtol={KERNEL_TOL['rtol']} "
        f"atol={KERNEL_TOL['atol']}) or f32-limited at every step; max "
        f"|2x2 - one chip| {gdiff[0]!r} ({gdiff[1]}); 2x2 update vs AdamW "
        f"of its gradient max |diff| {udiff!r} (bound rtol=atol="
        f"{UPDATE_TOL}); updated parameters vs one chip max |diff| "
        f"{pdiff[0]!r} ({pdiff[1]})")
    check(all(math.isfinite(x) for x in l_mesh + l_one), "non-finite loss")
    check(diff <= MESH_LOSS_TOL,
          f"2x2 mesh losses off the one-chip run by {diff!r}")

    # Batches, the edge table and the sampler state must span the mesh,
    # not sit committed to the first device. (Checked after the steps:
    # fetching a batch advances the train-negative stream.)
    bt = train_batch(p_mesh, 0)
    for key, v in bt.items():
        if isinstance(v, jax.Array):
            check(len(v.sharding.device_set) == 4,
                  f"batch tensor {key!r} lives on {v.sharding.device_set}")
    if on_tpu:  # the fused path engages (and exposes these) on TPU only
        check("edge_feat_table" in bt and "nbr_buf" in bt,
              "the 2x2 pipeline exposed no edge table / packed buffer")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0,
                   help="stream and weight seed")
    p.add_argument("--scale", type=float, default=1.0,
                   help="stream scale (1.0 = the full wikipedia-shaped "
                        "stream; smaller only for a CPU rehearsal)")
    p.add_argument("--four-chips", action="store_true",
                   help="run only the 2x2-mesh vs one-chip comparison")
    p.add_argument("--allow-cpu-rehearsal", action="store_true",
                   help="rehearse on the CPU (kernels in interpret mode "
                        "for the kernel-vs-oracle check)")
    args = p.parse_args(argv)

    from repro.utils.compile_cache import configure_compile_cache

    cache = configure_compile_cache()
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"device {device}; jax {jax.__version__}; compile cache {cache}")
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.allow_cpu_rehearsal:
        log(f"error: no TPU found (JAX platform is "
            f"{device['platform']!r}); this check runs on the chip only")
        return 1

    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(args, on_tpu)
    else:
        tgat_phase(args, on_tpu)
        gcn_phase(args, on_tpu)
    log(f"all phases passed in {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
