"""Production training driver with checkpoint/restart fault tolerance.

Three workload kinds, selected by ``--workload``:
  * ``tg``   — the paper's workload: CTDG link prediction (TGAT/TGN/...)
               on a synthetic TGB-like stream, optionally data-parallel via
               the shard_map DP trainer;
  * ``dtdg`` — DTDG snapshot link prediction through ``tg.Experiment``
               (scan-compiled pipeline) with per-chunk checkpoints and
               mid-epoch ``snapshot_cursor`` resume;
  * ``lm``   — small-scale LM training (any ``--arch``, reduced or scaled
               config) with the GSPMD train step.

Fault tolerance: async sharded checkpoints every ``--ckpt-every`` steps;
on startup the driver resumes from the newest checkpoint (``--resume``),
and data order is a pure function of (seed, step) so restarts are
deterministic. ``--simulate-failure N`` kills the process at step N to
exercise the restart path (used by tests/test_fault_tolerance.py).

Straggler mitigation at scale comes from fixed-shape steps (no ragged
work), host-side prefetch, and the elastic restore path (a lost pod =>
resume on the smaller mesh; shardings are re-derived from logical axes).
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils.compile_cache import configure_compile_cache


def train_tg(args) -> int:
    from repro.data import generate
    from repro.train import LinkPredictionTrainer
    from repro.distributed import checkpoint as ckpt

    data = generate(args.dataset, scale=args.data_scale)
    tr = LinkPredictionTrainer(
        args.model, data, batch_size=args.batch_size, k=args.k,
        eval_negatives=args.eval_negatives, seed=args.seed,
    )

    start_epoch = 0
    if args.resume and ckpt.latest_step(args.ckpt_dir) is not None:
        tree, step, extra = ckpt.restore(
            args.ckpt_dir,
            target={"params": tr.params, "opt": tr.opt_state},
        )
        tr.params, tr.opt_state = tree["params"], tree["opt"]
        start_epoch = extra.get("epoch", step) + 1
        print(f"[resume] restored epoch {start_epoch - 1} from {args.ckpt_dir}")

    writer = ckpt.AsyncCheckpointer(args.ckpt_dir, keep=3)
    for epoch in range(start_epoch, args.epochs):
        loss, secs = tr.train_epoch()
        mrr, _ = tr.evaluate("val") if args.eval_every and (
            epoch % args.eval_every == 0) else (float("nan"), 0)
        print(f"epoch {epoch}: loss={loss:.4f} mrr={mrr:.4f} ({secs:.1f}s)",
              flush=True)
        writer.save(epoch, {"params": tr.params, "opt": tr.opt_state},
                    extra_meta={"epoch": epoch, "loss": float(loss)})
        if args.simulate_failure is not None and epoch == args.simulate_failure:
            writer.wait()
            print("[failure-injection] exiting mid-run", flush=True)
            os._exit(42)
    writer.close()
    mrr, _ = tr.evaluate("test")
    print(f"final test MRR: {mrr:.4f}")
    return 0


def train_dtdg(args) -> int:
    """DTDG link workload through the ``tg.Experiment`` front door with
    per-chunk checkpoints: the scan pipeline's ``snapshot_cursor`` is
    written after every compiled chunk, ``--simulate-failure N`` kills the
    process after N chunks (mid-epoch), and ``--resume`` restores to that
    exact chunk boundary — final metrics are bit-identical to an
    uninterrupted run (tests/test_fault_tolerance.py)."""
    from repro import tg
    from repro.distributed import checkpoint as ckpt

    exp = tg.Experiment(
        task="link",
        data=tg.DataSpec(dataset=args.dataset, scale=args.data_scale,
                         discretization=args.discretization),
        model=tg.ModelSpec(name=args.model),
        train=tg.TrainSpec(epochs=args.epochs, seed=args.seed,
                           compiled=True, chunk_size=args.chunk_size),
    )
    pipe = exp.compile()

    start_epoch = 0
    if args.resume and ckpt.latest_step(args.ckpt_dir) is not None:
        step = pipe.restore_checkpoint(args.ckpt_dir)
        start_epoch = step // 100000
        print(f"[resume] restored step {step} "
              f"(epoch {start_epoch}, cursor {pipe.snapshot_cursor})",
              flush=True)

    chunks_done = 0
    for epoch in range(start_epoch, args.epochs):
        t0 = time.perf_counter()
        losses: list = []
        while True:
            chunk_losses = pipe.train_chunk()
            if chunk_losses is None:
                break
            losses.extend(chunk_losses)
            chunks_done += 1
            # Step encodes (epoch, cursor): unique, monotonic, and enough
            # to place a resume at the exact chunk boundary.
            pipe.save_checkpoint(args.ckpt_dir,
                                 epoch * 100000 + pipe.snapshot_cursor)
            if (args.simulate_failure is not None
                    and chunks_done == args.simulate_failure):
                print("[failure-injection] exiting mid-run", flush=True)
                os._exit(42)
        loss = float(np.mean(losses)) if losses else 0.0
        print(f"epoch {epoch}: loss={loss:.4f} "
              f"({time.perf_counter() - t0:.1f}s)", flush=True)
    mrr, _ = pipe.evaluate("test")
    print(f"final test MRR: {mrr:.4f}")
    return 0


def train_lm(args) -> int:
    from repro.configs import get_arch
    from repro.data import synthetic_token_batches
    from repro.distributed import checkpoint as ckpt
    from repro.models.lm import model as M
    from repro.optim import AdamWConfig
    from repro.train.lm_train import init_opt_state, make_train_step

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    key = jax.random.PRNGKey(args.seed)
    params = M.init(cfg, key)
    opt_state = init_opt_state(params)
    step_fn = jax.jit(make_train_step(cfg, AdamWConfig(lr=args.lr),
                                      kv_block=min(1024, args.seq_len)))

    start = 0
    if args.resume and ckpt.latest_step(args.ckpt_dir) is not None:
        tree, start_step, _ = ckpt.restore(
            args.ckpt_dir, target={"params": params, "opt": opt_state})
        params, opt_state = tree["params"], tree["opt"]
        start = start_step + 1
        print(f"[resume] restored step {start - 1}")

    writer = ckpt.AsyncCheckpointer(args.ckpt_dir, keep=3)
    gen = synthetic_token_batches(cfg.vocab_size, args.batch_size,
                                  args.seq_len, args.steps, seed=args.seed)
    t0 = time.perf_counter()
    for step, (tokens, labels) in enumerate(gen):
        if step < start:
            continue  # deterministic replay: skip consumed batches
        batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}
        if cfg.family in ("audio", "vlm"):
            batch["frontend"] = jnp.zeros(
                (args.batch_size, cfg.frontend_seq, cfg.d_model),
                jnp.dtype(cfg.compute_dtype))
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if step % args.log_every == 0:
            print(f"step {step}: loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"({time.perf_counter() - t0:.1f}s)", flush=True)
        if args.ckpt_every and step % args.ckpt_every == 0:
            writer.save(step, {"params": params, "opt": opt_state})
        if args.simulate_failure is not None and step == args.simulate_failure:
            writer.wait()
            print("[failure-injection] exiting mid-run", flush=True)
            os._exit(42)
    writer.close()
    print(f"done: final loss {float(metrics['loss']):.4f}")
    return 0


def main(argv: Optional[list] = None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", choices=["tg", "dtdg", "lm"], default="tg")
    p.add_argument("--ckpt-dir", default="checkpoints")
    p.add_argument("--resume", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--simulate-failure", type=int, default=None)
    # tg
    p.add_argument("--model", default="tgat")
    p.add_argument("--dataset", default="tiny")
    p.add_argument("--data-scale", type=float, default=1.0)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--batch-size", type=int, default=200)
    p.add_argument("--k", type=int, default=20)
    p.add_argument("--eval-negatives", type=int, default=20)
    p.add_argument("--eval-every", type=int, default=0)
    # dtdg
    p.add_argument("--discretization", default="h")
    p.add_argument("--chunk-size", type=int, default=4)
    # lm
    p.add_argument("--arch", default="qwen3-0.6b")
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--ckpt-every", type=int, default=20)
    args = p.parse_args(argv)
    configure_compile_cache()
    if args.workload == "tg":
        return train_tg(args)
    if args.workload == "dtdg":
        return train_dtdg(args)
    return train_lm(args)


if __name__ == "__main__":
    raise SystemExit(main())
