"""Explicit shard_map data-parallel trainer (DistTGL-style) for the TG
models — the distributed runtime for the paper's workload.

Temporal-graph training state is small (params ~1-10M) but *stateful*
(TGN memory, TPNet random features), so the scaling axis is data
parallelism over event streams with periodic state synchronization — the
DistTGL recipe. Here:

  * the global event batch is sharded over the 'data' mesh axis (each
    shard is a contiguous sub-stream, preserving per-shard time order);
  * gradients are psum-averaged inside shard_map, optionally compressed
    (bf16 / int8 + error feedback, see compression.py);
  * model state (e.g. TGN memory) is synchronized by a masked psum: nodes
    touched on exactly one shard take that shard's value; nodes touched on
    several take the mean (staleness is bounded by one batch — the
    DistTGL trade-off);
  * the optimizer update runs replicated (params are replicated in DP).

Gradient-accumulation microbatching overlaps the per-microbatch
reduce-scatter with the next microbatch's backward (XLA latency hiding
does the interleaving once both are in the same program).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import compression as comp
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.distributed.sharding import sync_state_masked_psum


class DataParallelTrainer:
    """shard_map DP wrapper around a per-shard loss function.

    loss_fn(params, state, batch_shard) -> (loss, (new_state, touched))
      ``touched``: bool mask (num_nodes,) of state rows this shard updated
      (None for stateless models — pass state={} and touched=None).
    """

    def __init__(
        self,
        loss_fn: Callable,
        mesh: Mesh,
        opt_cfg: AdamWConfig = AdamWConfig(lr=1e-4),
        axis: str = "data",
        compression: str = "none",
        accum_steps: int = 1,
    ):
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.axis = axis
        self.opt_cfg = opt_cfg
        self.compression = compression
        self.accum_steps = accum_steps
        self._step = None

    def init(self, params):
        opt_state = adamw_init(params)
        err = comp.zeros_like_error(params) if self.compression == "int8_ef" else None
        return opt_state, err

    def build_step(self, stateful: bool):
        axis = self.axis
        scheme = self.compression
        opt_cfg = self.opt_cfg
        loss_fn = self.loss_fn
        accum = self.accum_steps

        def shard_step(params, opt_state, err, state, batch):
            # batch leaves: (accum, per_shard_B, ...) inside shard_map
            def one_micro(carry, micro):
                grads_acc, loss_acc, state = carry
                (loss, (state, touched)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, state, micro)
                grads_acc = jax.tree.map(jnp.add, grads_acc, grads)
                return (grads_acc, loss_acc + loss, state), touched

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss, state), touched = jax.lax.scan(
                one_micro, (zeros, 0.0, state), batch)
            grads = jax.tree.map(lambda g: g / accum, grads)
            loss = loss / accum

            # compressed gradient all-reduce
            wire, err, _ = comp.compress_grads(grads, err, scheme)
            grads = comp.psum_compressed(wire, scheme, axis)
            loss = jax.lax.pmean(loss, axis)

            # DistTGL-style state sync: mean over shards that touched a row
            if stateful and touched is not None:
                touched_any = touched.any(0)  # over accum steps
                state = sync_state_masked_psum(state, touched_any, axis)

            params_new, opt_state = adamw_update(params, grads, opt_state, opt_cfg)
            return params_new, opt_state, err, state, loss

        pspec = P()  # replicated params/opt/err/state
        bspec = jax.tree.map(lambda _: P(None, self.axis), {"x": 0})["x"]

        smapped = jax.shard_map(
            shard_step,
            mesh=self.mesh,
            in_specs=(pspec, pspec, pspec, pspec, P(None, self.axis)),
            out_specs=(pspec, pspec, pspec, pspec, P()),
            check_vma=False,
        )
        self._step = jax.jit(smapped)
        return self._step

    def step(self, params, opt_state, err, state, batch):
        """batch leaves: (accum, global_B, ...) — sharded over axis 1."""
        if self._step is None:
            raise RuntimeError("call build_step() first")
        if err is None:
            err = jax.tree.map(lambda p: jnp.zeros((1,), jnp.float32), {})
        return self._step(params, opt_state, err, state, batch)
