"""The shared training engine behind every TG task quadrant.

This module owns the machinery that used to be duplicated (or hand-rolled
per example) across ``LinkPredictionTrainer`` and ``SnapshotLinkTrainer``:

  * ``CTDGLinkPipeline``  — event-stream link prediction (TGB link recipe,
    optional device-resident sampling + ``PrefetchLoader``, jitted steps);
  * ``DTDGLinkPipeline``  — scan-compiled snapshot link prediction
    (``SnapshotTensor`` + ``lax.scan``; ``compiled=False`` keeps the
    per-snapshot jitted loop as the bit-parity oracle);
  * ``TrainLoop``         — the epoch engine: runs ``train_epoch`` /
    ``evaluate`` / ``save_checkpoint`` on any pipeline with the standard
    surface, applying eval and checkpoint cadences and recording history;
  * the checkpoint bundle helpers (``save_bundle`` / ``restore_bundle`` /
    ``restore_with_saved_hooks``) and ``weighted_mrr`` shared by all
    pipelines.

``repro.tg.Experiment`` is the declarative front door that assembles these
pipelines from specs; ``repro.train.tg_trainer`` keeps the legacy trainer
names as thin deprecated shims over the same classes. The node-property
pipelines live in ``repro.train.nodeprop`` and run through the same
``TrainLoop`` surface. See ``docs/experiment.md``.

Pipeline surface (duck-typed, consumed by ``TrainLoop``):

  ``train_epoch() -> (mean_loss, seconds)``
  ``evaluate(split) -> (metric, seconds)``      # split in {train,val,test}
  ``save_checkpoint(ckpt_dir, step) -> path``
  ``restore_checkpoint(ckpt_dir, step=None) -> step``
"""

from __future__ import annotations

import contextlib
import time
from functools import partial
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    DGData,
    DGraph,
    DGDataLoader,
    PrefetchLoader,
    RECIPE_DTDG_SNAPSHOT,
    RECIPE_TGB_LINK,
    RecipeRegistry,
    TimeDelta,
    TRAIN_KEY,
    EVAL_KEY,
    snapshot_tensor,
)
from repro.distributed import checkpoint as ckpt
from repro.models.tg import dygformer, graphmixer, snapshot, tgat, tgn, tpnet
from repro.obs import MemorySink, Telemetry
from repro.models.tg.common import bce_link_loss, link_decoder
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.tg.specs import SamplerSpec
from repro.train.metrics import mrr

CTDG_STATELESS = {"tgat", "graphmixer", "dygformer"}
CTDG_STATEFUL = {"tgn", "tpnet"}
CTDG_LINK_MODELS = CTDG_STATELESS | CTDG_STATEFUL


# ----------------------------------------------------------------------
# Shared checkpoint machinery
# ----------------------------------------------------------------------
def restore_with_saved_hooks(ckpt_dir, step, target):
    """Two-phase checkpoint restore with a checkpoint-shaped hooks subtree.

    The hooks state is checkpoint-dependent (e.g. the uniform samplers'
    counter-only mode drops the CSR leaves), so a target prototype built
    from the *current* hook state can demand leaves the checkpoint never
    saved. Read the flat checkpoint once, reassemble the hooks subtree
    that was actually written (``<group>/<idx>/<state_key>`` keys with flat
    array leaves — the shared contract), and assemble the rest structurally
    from the already-loaded leaves; the samplers' ``load_state_dict``
    accepts either form.
    """
    flat, step, meta = ckpt.restore(ckpt_dir, step, target=None)
    hooks: Dict[str, Dict] = {}
    for k, v in flat.items():
        if k.startswith("hooks/"):
            group, leaf = k[len("hooks/"):].rsplit("/", 1)
            hooks.setdefault(group, {})[leaf] = v
    target = dict(target)
    target["hooks"] = hooks
    return ckpt.assemble(flat, target), step, meta


def save_bundle(ckpt_dir: str, step: int, tree: Dict[str, Any],
                model_name: str, **extra_meta) -> str:
    """Write a pipeline checkpoint bundle (atomic step directory).

    ``tree`` is the composable ``{params, opt_state[, model_state],
    hooks[, pipeline]}`` contract every pipeline shares; ``model_name``
    (plus any ``extra_meta``) rides the sidecar metadata so restores can
    refuse mismatched models. Returns the written path.
    """
    return ckpt.save(ckpt_dir, step, tree,
                     extra_meta={"model_name": model_name, **extra_meta})


def restore_bundle(ckpt_dir: str, step: Optional[int], target: Dict[str, Any],
                   model_name: str):
    """Restore a bundle written by ``save_bundle`` into ``target``'s
    structure (hooks subtree checkpoint-shaped; see
    ``restore_with_saved_hooks``), validating the model name. Returns
    ``(tree, step)``.
    """
    tree, step, meta = restore_with_saved_hooks(ckpt_dir, step, target)
    if meta.get("model_name") not in (None, model_name):
        raise ValueError(
            f"checkpoint is for model {meta['model_name']!r}, "
            f"pipeline is {model_name!r}"
        )
    return tree, step


def weighted_mrr(pos_rows, neg_rows, mask_rows) -> float:
    """Per-row MRR weighted by valid predictions — shared by the scanned
    and loop DTDG paths so their aggregation is bit-identical."""
    out, wsum = 0.0, 0.0
    for pos, neg, m in zip(pos_rows, neg_rows, mask_rows):
        w = float(np.asarray(m).sum())
        if w:
            out += mrr(pos, neg, m) * w
            wsum += w
    return float(out / max(wsum, 1.0))


# ----------------------------------------------------------------------
# The epoch engine
# ----------------------------------------------------------------------
def history_from_records(records) -> Dict[str, Any]:
    """Rebuild a ``TrainLoop.fit`` history dict from telemetry records.

    Consumes the ``train/epoch`` / ``train/eval`` / ``train/ckpt`` span
    records one ``fit`` emits (in order) and returns the exact history
    contract — ``{"loss", "train_secs", "eval", "ckpts"}`` with the same
    values the pipeline produced (they ride the span attrs verbatim; span
    durations are *not* used, so the numbers are bit-identical to the
    pre-telemetry hand-rolled dict). Non-span and unrelated records are
    ignored, so a shared sink's full stream can be passed unfiltered.
    """
    history: Dict[str, Any] = {"loss": [], "train_secs": [], "eval": [],
                               "ckpts": []}
    for r in records:
        if r.get("kind") != "span":
            continue
        attrs = r.get("attrs", {})
        if r["name"] == "train/epoch":
            history["loss"].append(attrs["loss"])
            history["train_secs"].append(attrs["secs"])
        elif r["name"] == "train/eval":
            history["eval"].append((attrs["epoch"], attrs["metric"]))
        elif r["name"] == "train/ckpt":
            history["ckpts"].append(attrs["path"])
    return history


class TrainLoop:
    """Multi-epoch driver over any pipeline with the standard surface.

    ``fit`` runs ``epochs`` training epochs, evaluating ``eval_split``
    every ``eval_every`` epochs (0 = never) and writing a checkpoint to
    ``ckpt_dir`` every ``ckpt_every`` epochs (0 = never), and returns a
    history dict::

        {"loss": [...], "train_secs": [...],
         "eval": [(epoch, metric), ...], "ckpts": [path, ...]}

    The loop is deliberately dumb — all task/pipeline intelligence lives in
    the pipeline object — which is what lets the CTDG/DTDG × link/node
    quadrants share one engine.

    Every ``fit`` emits ``train/epoch`` / ``train/eval`` / ``train/ckpt``
    spans through ``telemetry`` (defaulting to the pipeline's own
    ``Telemetry``, so one spec-configured sink sees the whole run), and
    the returned history is itself rebuilt from those records
    (:func:`history_from_records`) — the records are the source of truth,
    not a parallel bookkeeping path.
    """

    def __init__(self, pipeline, telemetry: Optional[Telemetry] = None):
        self.pipeline = pipeline
        if telemetry is None:
            telemetry = getattr(pipeline, "telemetry", None)
        # A private instance when neither the caller nor the pipeline has
        # one: fit() attaches its history sink here, which must never
        # mutate a shared singleton.
        self.telemetry = telemetry if telemetry is not None else Telemetry()

    def fit(self, epochs: int = 1, eval_every: int = 0,
            eval_split: str = "val", ckpt_dir: Optional[str] = None,
            ckpt_every: int = 0, log=None) -> Dict[str, Any]:
        """Run the epoch loop; see the class docstring for the contract."""
        tel = self.telemetry
        # Tee: the history comes from the records (its own ones only, so
        # the per-batch spans of a long fit are not kept in memory).
        mem = tel.attach(MemorySink(prefixes=("train/",)))
        try:
            for epoch in range(epochs):
                with tel.span("train/epoch", epoch=epoch) as sp:
                    loss, secs = self.pipeline.train_epoch()
                    sp["loss"], sp["secs"] = loss, secs
                if log is not None:
                    log(f"epoch {epoch}: loss={loss:.4f} ({secs:.1f}s)")
                if eval_every and (epoch + 1) % eval_every == 0:
                    with tel.span("train/eval", epoch=epoch,
                                  split=eval_split) as sp:
                        metric, _ = self.pipeline.evaluate(eval_split)
                        sp["metric"] = metric
                    if log is not None:
                        log(f"epoch {epoch}: {eval_split} "
                            f"metric={metric:.4f}")
                if ckpt_dir and ckpt_every and (epoch + 1) % ckpt_every == 0:
                    with tel.span("train/ckpt", epoch=epoch) as sp:
                        sp["path"] = self.pipeline.save_checkpoint(
                            ckpt_dir, epoch)
        finally:
            tel.detach(mem)
        return history_from_records(mem.records)


# ----------------------------------------------------------------------
# CTDG link prediction: event-stream pipeline
# ----------------------------------------------------------------------
class CTDGLinkPipeline:
    """CTDG link-prediction over the TGB link recipe.

    Event-iterated batches feed jitted train/eval steps for the CTDG model
    zoo (TGAT, TGN, GraphMixer, DyGFormer, TPNet): random train negatives,
    one-vs-many eval negatives, recency/uniform temporal neighbors,
    padding, device transfer.

    The sampling strategy comes from a ``repro.tg.SamplerSpec``:
    ``device=True`` switches to the device-resident pipeline (accelerator-
    resident sampler state with jit-compiled update/sample inside the
    hooks, and the loader wrapped in a ``PrefetchLoader`` that stages the
    *next* batch while the current jitted step runs). The host-numpy
    default doubles as the parity oracle in tests.

    ``SamplerSpec.shards`` additionally shards the device sampler state
    row-wise by node id over a 1-D mesh (``shard_map`` update/sample;
    bit-identical outputs), stages batches mesh-replicated, and runs the
    jitted steps replicated over the same mesh — see ``docs/sharding.md``.

    ``data_shards > 1`` composes the data and node axes into one 2-D
    ``("data", "nodes")`` mesh of ``data_shards × (SamplerSpec.shards or
    1)`` devices: each train step slices the event batch into contiguous
    time-ordered sub-streams over the data axis (gradients psum'd, the
    loss normalized by the global term count, TGN memory synchronized by
    the DistTGL masked psum) while sampler buffers/CSR stay partitioned
    over the node axis. With ``fused`` enabled the per-shard attention
    runs shard-aware (``fused_temporal_layer_sharded``) over each node
    shard's local buffer block, assembled exactly by a psum over the node
    axis — so one step scales FLOPs (data axis) and sampler HBM (node
    axis) together. ``fused`` forwards to the TGAT/TGN ``link_scores``
    (e.g. ``"ref"`` forces the fused math on CPU for parity tests).
    """

    def __init__(
        self,
        model_name: str,
        data: DGData,
        batch_size: int = 200,
        k: int = 20,
        lr: Optional[float] = None,
        eval_negatives: int = 20,
        seed: int = 0,
        model_kwargs: Optional[Dict[str, Any]] = None,
        device_sampling: bool = False,
        prefetch: int = 2,
        sampler: str = "recency",
        uniform_checkpoint_adjacency: bool = True,
        sampler_spec: Optional[SamplerSpec] = None,
        val_ratio: float = 0.15,
        test_ratio: float = 0.15,
        data_shards: int = 1,
        fused=None,
        store=None,
        telemetry: Optional[Telemetry] = None,
    ):
        if model_name not in CTDG_LINK_MODELS:
            raise ValueError(f"unknown CTDG model {model_name!r}")
        # Per-pipeline telemetry (docs/observability.md): a fresh disabled
        # instance by default so TrainLoop can tee sinks onto it safely.
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        spec = sampler_spec or SamplerSpec(
            kind=sampler, k=k, device=device_sampling, prefetch=prefetch,
            checkpoint_adjacency=uniform_checkpoint_adjacency,
        )
        self.model_name = model_name
        self.data = data
        # Out-of-core handle (repro.storage.EventStore). When set, the
        # uniform adjacency is built by the streaming two-pass CSR (O(chunk)
        # resident) and loaders release memmap pages after every batch.
        self._store = store
        self.batch_size = batch_size
        self.sampler_spec = spec
        self.device_sampling = spec.device
        self.prefetch = spec.prefetch
        self.data_shards = int(data_shards)
        self.fused = fused
        if self.data_shards < 1:
            raise ValueError("data_shards must be a positive integer")
        if fused is not None and model_name not in ("tgat", "tgn"):
            raise ValueError(
                f"fused= applies to the TGAT/TGN fused attention path; "
                f"{model_name!r} has no fused twin"
            )
        if self.data_shards > 1:
            if not spec.device:
                raise ValueError(
                    "data_shards > 1 requires SamplerSpec(device=True) — "
                    "the 2-D mesh step assumes device-staged batches and "
                    "mesh-placed sampler state (docs/sharding.md)"
                )
            if batch_size % self.data_shards:
                raise ValueError(
                    f"batch_size {batch_size} must be divisible by "
                    f"data_shards {self.data_shards} (each data shard takes "
                    f"a contiguous time-ordered sub-stream of the batch)"
                )
            if model_name == "tpnet":
                raise ValueError(
                    "data_shards > 1 supports tgat/tgn/graphmixer/dygformer;"
                    " tpnet's sketch state has no masked-psum sync recipe"
                )
        # Resolve expose_buffer early: it decides whether the sharded fused
        # path (and hence the 2-D shard_map step) is in play. Only TGAT/TGN
        # consume the exposed packed buffer; under a mesh, exposure is an
        # opt-in for the shard-aware fused layer, so auto-enable it exactly
        # when the fused path can engage (explicit fused= or TPU backend).
        expose = spec.expose_buffer
        if expose is None and model_name not in ("tgat", "tgn"):
            expose = False
        if expose is None and (spec.shards or self.data_shards > 1):
            expose = bool(self.fused) or jax.default_backend() == "tpu"
        self._expose_buffer = expose
        # Multi-device meshes (docs/sharding.md): data_shards composes the
        # 2-D ("data", "nodes") mesh — event sub-streams over the data
        # axis, sampler state over the node axis; SamplerSpec.shards alone
        # keeps the 1-D node mesh with replicated jitted steps. The 2-D
        # shard_map step is also required whenever a *sharded* packed
        # buffer rides the batch (expose_buffer with shards), since only
        # ``fused_temporal_layer_sharded`` inside a shard_map can read it.
        self._mesh = None
        self._replicated = None
        self._data_axis = None
        self._node_axis = None
        self._use_2d = self.data_shards > 1 or bool(
            spec.shards and expose and spec.kind == "recency"
            and model_name in ("tgat", "tgn")
        )
        recipe_axis = spec.mesh_axis
        if self._use_2d:
            from repro.distributed.sharding import (
                make_2d_mesh,
                replicated_sharding,
            )

            self._mesh = make_2d_mesh(self.data_shards, spec.shards or 1)
            self._replicated = replicated_sharding(self._mesh)
            self._data_axis, self._node_axis = "data", "nodes"
            recipe_axis = "nodes"
        elif spec.shards:
            from repro.distributed.sharding import (
                make_node_mesh,
                replicated_sharding,
            )

            self._mesh = make_node_mesh(spec.shards, spec.mesh_axis)
            self._replicated = replicated_sharding(self._mesh)
        self.train_data, self.val_data, self.test_data = data.split(
            val_ratio, test_ratio
        )
        kwargs = dict(model_kwargs or {})
        k = spec.k

        d_edge = data.edge_feat_dim
        n = data.num_nodes
        key = jax.random.PRNGKey(seed)

        num_hops = 1
        if model_name == "tgat":
            self.cfg = tgat.TGATConfig(num_nodes=n, d_edge=d_edge, k=k, **kwargs)
            num_hops = min(2, self.cfg.num_layers)
            self.params = tgat.init(key, self.cfg)
            self._scores = partial(tgat.link_scores, cfg=self.cfg)
        elif model_name == "graphmixer":
            self.cfg = graphmixer.GraphMixerConfig(num_nodes=n, d_edge=d_edge, k=k, **kwargs)
            self.params = graphmixer.init(key, self.cfg)
            self._scores = partial(graphmixer.link_scores, cfg=self.cfg)
        elif model_name == "dygformer":
            self.cfg = dygformer.DyGFormerConfig(num_nodes=n, d_edge=d_edge, k=k, **kwargs)
            self.params = dygformer.init(key, self.cfg)
            self._scores = partial(dygformer.link_scores, cfg=self.cfg)
        elif model_name == "tgn":
            self.cfg = tgn.TGNConfig(num_nodes=n, d_edge=d_edge, k=k, **kwargs)
            self.params = tgn.init(key, self.cfg)
            self.model_state = tgn.init_state(self.cfg)
        elif model_name == "tpnet":
            self.cfg = tpnet.TPNetConfig(num_nodes=n, **kwargs)
            self.params = tpnet.init(key, self.cfg)
            self.model_state = tpnet.init_state(self.params, self.cfg)
        if spec.num_hops is not None:
            num_hops = spec.num_hops

        needs_nbrs = model_name != "tpnet"
        self.manager = RecipeRegistry.build(
            RECIPE_TGB_LINK,
            num_nodes=n,
            spec=SamplerSpec(
                kind=spec.kind, k=self.cfg.k if needs_nbrs else 1,
                num_hops=num_hops, device=spec.device,
                checkpoint_adjacency=spec.checkpoint_adjacency,
                expose_buffer=self._expose_buffer, prefetch=spec.prefetch,
                shards=spec.shards, mesh_axis=recipe_axis,
                partition=spec.partition,
            ),
            mesh=self._mesh,
            mesh_axis=recipe_axis,
            batch_size=batch_size,
            eval_negatives=eval_negatives,
            # Full-stream features: sampled nbr_eids are global event
            # indices (the loader offsets sliced splits by their
            # ``eid_offset``), so the lookup table must cover val/test
            # warm-up too (the train rows are the identical prefix).
            edge_feats=data.edge_feats if d_edge else None,
            edge_feat_dim=d_edge,
            seed=seed,
        )
        self.manager.telemetry = self.telemetry
        if spec.kind == "uniform":
            # The uniform samplers draw from a static CSR-by-time adjacency;
            # build it once over the full stream — the strict t < query_t
            # filter at sample time keeps it leak-free.
            from repro.core.tg_hooks import (
                DeviceUniformNeighborHook,
                UniformNeighborHook,
            )

            for hook in self.manager.hooks():
                if isinstance(hook, (UniformNeighborHook,
                                     DeviceUniformNeighborHook)):
                    if self._store is not None:
                        hook.build_from_store(self._store)
                    else:
                        hook.build(data.src, data.dst, data.edge_t,
                                   np.arange(len(data.src), dtype=np.int64))

        # Node rows owned per shard of the sharded packed buffer — the
        # ``rows_per_shard`` handed to ``fused_temporal_layer_sharded`` by
        # the 2-D step (None without a node-sharded recency sampler).
        self._buf_rows = None
        if self._node_axis is not None:
            from repro.core.tg_hooks import DeviceRecencyNeighborHook

            for hook in self.manager.hooks():
                if isinstance(hook, DeviceRecencyNeighborHook):
                    self._buf_rows = hook.sampler.rows_per_shard

        self.opt_cfg = AdamWConfig(lr=1e-4 if lr is None else lr)
        self.opt_state = adamw_init(self.params)
        self._place_replicated()
        self._build_steps()

    # ------------------------------------------------------------------
    def _place_replicated(self):
        """Commit params/optimizer (and recurrent model) state replicated
        onto the sampler mesh, so the jitted steps see one device set
        (sharded-sampling pipelines only; no-op without a mesh)."""
        if self._mesh is None:
            return
        self.params = jax.device_put(self.params, self._replicated)
        self.opt_state = jax.device_put(self.opt_state, self._replicated)
        if self.model_name in CTDG_STATEFUL:
            self.model_state = jax.device_put(self.model_state,
                                              self._replicated)

    def _build_steps(self):
        if self._use_2d:
            self._build_steps_2d()
            return
        name, B = self.model_name, self.batch_size
        skw = {} if self.fused is None else {"fused": self.fused}

        if name in CTDG_STATELESS:

            def loss_fn(params, batch):
                pos, neg = self._scores(params, batch=batch, batch_size=B,
                                        **skw)
                return bce_link_loss(pos, neg, batch["batch_mask"])

            @jax.jit
            def train_step(params, opt_state, batch):
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
                params, opt_state = adamw_update(params, grads, opt_state, self.opt_cfg)
                return params, opt_state, loss

            @jax.jit
            def eval_step(params, batch):
                return self._scores(params, batch=batch, batch_size=B, **skw)

            self._train_step, self._eval_step = train_step, eval_step

        else:
            score_fn = tgn.link_scores if name == "tgn" else tpnet.link_scores
            cfg = self.cfg

            def loss_fn(params, state, batch):
                (pos, neg), new_state = score_fn(params, cfg, state, batch, B,
                                                 **skw)
                return bce_link_loss(pos, neg, batch["batch_mask"]), new_state

            @jax.jit
            def train_step(params, opt_state, state, batch):
                (loss, new_state), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, state, batch
                )
                params, opt_state = adamw_update(params, grads, opt_state, self.opt_cfg)
                return params, opt_state, new_state, loss

            @jax.jit
            def eval_step(params, state, batch):
                return score_fn(params, cfg, state, batch, B, **skw)

            self._train_step, self._eval_step = train_step, eval_step

    # -- 2-D mesh steps (docs/sharding.md) ------------------------------
    def _seed_perm(self, S: int) -> np.ndarray:
        """Shard-major permutation of the stacked seed axis.

        Seed-aligned tensors are stacked ``[src (B) | dst (B) | neg
        (B*Nn)]``; slicing that layout over the data axis would hand shard
        0 nothing but src rows. This (static) permutation reorders rows
        shard-major so each contiguous ``1/data_shards`` slice is that
        shard's own ``[src_l | dst_l | neg_l]`` stack — exactly the seed
        layout the models expect at batch size ``B/data_shards``.
        """
        B, ds = self.batch_size, self.data_shards
        nn = (S - 2 * B) // B
        bl = B // ds
        parts = []
        for s in range(ds):
            lo, hi = s * bl, (s + 1) * bl
            parts.append(np.arange(lo, hi))
            parts.append(B + np.arange(lo, hi))
            if nn:
                parts.append(2 * B + np.arange(lo * nn, hi * nn))
        return np.concatenate(parts).astype(np.int32)

    def _make_2d_step(self, kind: str, bt: Dict[str, Any]):
        """Build one jitted 2-D ``shard_map`` step for this batch signature.

        Batch tensors are routed by leading dimension: event-aligned
        ``(B, ...)`` tensors slice directly over the data axis (the batch
        is time-ordered, so equal slices are contiguous time-ordered
        sub-streams); seed-aligned ``(S, ...)`` and frontier-aligned
        ``(S*K, ...)`` tensors are permuted shard-major first
        (``_seed_perm``); ``nbr_buf`` splits over the node axis; the edge
        table, params, optimizer and model state stay replicated. Each
        shard optimizes ``local_loss_sum / global_denominator`` so the
        psum'd gradient equals the single-device gradient; the optimizer
        update runs replicated inside the shard_map.
        """
        from jax.sharding import PartitionSpec as P

        from repro.distributed.sharding import sync_state_masked_psum
        from repro.models.tg.common import bce_link_loss_parts

        mesh = self._mesh
        daxis, naxis = self._data_axis, self._node_axis
        ds, B = self.data_shards, self.batch_size
        Bl = B // ds
        S = int(np.shape(bt["seed_nodes"])[0]) if "seed_nodes" in bt else -1
        perm = self._seed_perm(S) if (S > 0 and ds > 1) else None

        perms: Dict[str, Optional[np.ndarray]] = {}
        specs: Dict[str, P] = {}
        for key, v in bt.items():
            shp = tuple(np.shape(v))
            perms[key] = None
            if key == "nbr_buf":
                specs[key] = P(naxis)
            elif key == "edge_feat_table" or not shp:
                specs[key] = P()
            elif shp[0] == B:
                specs[key] = P(daxis)
            elif S > 0 and shp[0] % S == 0:
                if perm is not None:
                    m = shp[0] // S
                    perms[key] = perm if m == 1 else (
                        perm[:, None] * m + np.arange(m, dtype=np.int32)
                    ).reshape(-1)
                specs[key] = P(daxis)
            else:
                specs[key] = P()

        def prep(batch):
            return {k: (v if perms[k] is None else v[perms[k]])
                    for k, v in batch.items()}

        kw = {}
        if self.model_name in ("tgat", "tgn"):
            kw["fused"] = self.fused
            if "nbr_buf" in bt and self._buf_rows is not None:
                kw["node_axis"] = naxis
                kw["buf_rows"] = self._buf_rows
        opt_cfg = self.opt_cfg
        rep = P()

        if self.model_name in CTDG_STATELESS:
            scores = self._scores

            def train_body(params, opt_state, pb):
                def objective(p):
                    pos, neg = scores(p, batch=pb, batch_size=Bl, **kw)
                    num, den = bce_link_loss_parts(pos, neg,
                                                   pb["batch_mask"])
                    D = jnp.maximum(jax.lax.psum(den, daxis), 1.0)
                    return num / D, (num, den)

                (_, (num, den)), grads = jax.value_and_grad(
                    objective, has_aux=True)(params)
                grads = jax.tree.map(
                    lambda g: jax.lax.psum(g, daxis), grads)
                loss = jax.lax.psum(num, daxis) / jnp.maximum(
                    jax.lax.psum(den, daxis), 1.0)
                params, opt_state = adamw_update(params, grads, opt_state,
                                                 opt_cfg)
                return params, opt_state, loss

            def eval_body(params, pb):
                return scores(params, batch=pb, batch_size=Bl, **kw)

            if kind == "train":
                smapped = jax.shard_map(
                    train_body, mesh=mesh, in_specs=(rep, rep, specs),
                    out_specs=(rep, rep, rep), check_vma=False)
                return jax.jit(lambda p, o, b: smapped(p, o, prep(b)))
            smapped = jax.shard_map(
                eval_body, mesh=mesh, in_specs=(rep, specs),
                out_specs=(P(daxis), P(daxis)), check_vma=False)
            return jax.jit(lambda p, b: smapped(p, prep(b)))

        score_fn = tgn.link_scores
        cfg = self.cfg

        def touched_rows(pb):
            # Node rows this data shard's events update — the masked-psum
            # sync mask (padded rows excluded via batch_mask).
            nodes = jnp.concatenate([pb["src"], pb["dst"]])
            mm = jnp.concatenate([pb["batch_mask"], pb["batch_mask"]])
            return jnp.zeros(cfg.num_nodes, bool).at[nodes].max(mm)

        def train_body(params, opt_state, state, pb):
            def objective(p):
                (pos, neg), new_state = score_fn(p, cfg, state, pb, Bl, **kw)
                num, den = bce_link_loss_parts(pos, neg, pb["batch_mask"])
                D = jnp.maximum(jax.lax.psum(den, daxis), 1.0)
                return num / D, (num, den, new_state)

            (_, (num, den, new_state)), grads = jax.value_and_grad(
                objective, has_aux=True)(params)
            grads = jax.tree.map(lambda g: jax.lax.psum(g, daxis), grads)
            loss = jax.lax.psum(num, daxis) / jnp.maximum(
                jax.lax.psum(den, daxis), 1.0)
            new_state = sync_state_masked_psum(
                new_state, touched_rows(pb), daxis)
            params, opt_state = adamw_update(params, grads, opt_state,
                                             opt_cfg)
            return params, opt_state, new_state, loss

        def eval_body(params, state, pb):
            (pos, neg), new_state = score_fn(params, cfg, state, pb, Bl,
                                             **kw)
            new_state = sync_state_masked_psum(
                new_state, touched_rows(pb), daxis)
            return (pos, neg), new_state

        if kind == "train":
            smapped = jax.shard_map(
                train_body, mesh=mesh, in_specs=(rep, rep, rep, specs),
                out_specs=(rep, rep, rep, rep), check_vma=False)
            return jax.jit(lambda p, o, s, b: smapped(p, o, s, prep(b)))
        smapped = jax.shard_map(
            eval_body, mesh=mesh, in_specs=(rep, rep, specs),
            out_specs=((P(daxis), P(daxis)), rep), check_vma=False)
        return jax.jit(lambda p, s, b: smapped(p, s, prep(b)))

    def _build_steps_2d(self):
        """Install 2-D dispatchers with the standard step signatures.

        Steps are built lazily per batch signature (train and eval batches
        differ in the negatives width, hence in every seed-aligned shape)
        and memoized, so each shape still compiles exactly once.
        """
        cache: Dict[Any, Any] = {}

        def get(kind, bt):
            sig = (kind, tuple(sorted(
                (k, tuple(np.shape(v))) for k, v in bt.items())))
            if sig not in cache:
                cache[sig] = self._make_2d_step(kind, bt)
            return cache[sig]

        if self.model_name in CTDG_STATELESS:
            self._train_step = lambda p, o, bt: get("train", bt)(p, o, bt)
            self._eval_step = lambda p, bt: get("eval", bt)(p, bt)
        else:
            self._train_step = (
                lambda p, o, s, bt: get("train", bt)(p, o, s, bt))
            self._eval_step = lambda p, s, bt: get("eval", bt)(p, s, bt)

    # ------------------------------------------------------------------
    def _loader(self, data: DGData):
        # With an out-of-core store, drop its resident pages after each
        # batch is handed off — hooks copy what they keep, so the epoch's
        # peak RSS stays near one window of the stream.
        on_batch = None
        if self._store is not None:
            store, tel = self._store, self.telemetry

            def on_batch():
                store.release()
                tel.count("storage/windows_released")

        loader = DGDataLoader(DGraph(data), self.manager,
                              batch_size=self.batch_size, on_batch=on_batch)
        if self.device_sampling:
            # Overlap hook pipeline + host->device staging of batch i+1 with
            # the jitted step on batch i (double-buffered by default). With
            # a sampler mesh, batches are staged with the mesh-replicated
            # NamedSharding so they land on the sharded state's device set.
            return PrefetchLoader(loader, device=self._replicated,
                                  prefetch=self.prefetch,
                                  telemetry=self.telemetry)
        return loader

    def _batch_tensors(self, batch) -> Dict[str, Any]:
        return {k: batch[k] for k in batch.keys()}

    def reset_epoch_state(self):
        """Clear hook/sampler state (+ recurrent model state) for an epoch.

        Fresh model state is committed where the steps leave it, with the
        batches (the mesh's replicated sharding, else the first device),
        so a new epoch's first step reuses the compiled step."""
        self.manager.reset_state()
        if self.model_name == "tgn":
            self.model_state = tgn.init_state(self.cfg)
        elif self.model_name == "tpnet":
            self.model_state = tpnet.init_state(self.params, self.cfg)
        if self.model_name in CTDG_STATEFUL:
            self.model_state = jax.device_put(
                self.model_state, jax.devices()[0] if self._mesh is None
                else self._replicated)

    # -- checkpointing ---------------------------------------------------
    # The hook/sampler buffers (host numpy or device JAX pytree — both
    # expose the same state_dict contract) ride along with params/optimizer
    # state, so a restored run resumes mid-stream with warm neighbor state.
    def save_checkpoint(self, ckpt_dir: str, step: int) -> str:
        """Write a checkpoint (atomic step directory). Returns its path."""
        tree = {
            "params": self.params,
            "opt_state": self.opt_state,
            "hooks": self.manager.state_dict(),
        }
        if self.model_name in CTDG_STATEFUL:
            tree["model_state"] = self.model_state
        return save_bundle(ckpt_dir, step, tree, self.model_name)

    def restore_checkpoint(self, ckpt_dir: str, step: Optional[int] = None) -> int:
        """Restore params/opt/hook (+ model) state; returns the step."""
        target = {
            "params": self.params,
            "opt_state": self.opt_state,
        }
        if self.model_name in CTDG_STATEFUL:
            target["model_state"] = self.model_state
        tree, step = restore_bundle(ckpt_dir, step, target, self.model_name)
        self.params = tree["params"]
        self.opt_state = tree["opt_state"]
        self.manager.load_state_dict(tree["hooks"])
        if self.model_name in CTDG_STATEFUL:
            self.model_state = tree["model_state"]
        # Checkpoints are mesh-agnostic (canonical host layouts); re-commit
        # the restored trees onto this pipeline's mesh, whatever mesh (or
        # none) wrote them.
        self._place_replicated()
        return step

    @property
    def train_step(self):
        """The train step ``train_epoch`` applies to each batch:
        ``(params, opt_state, batch) -> (params, opt_state, loss)``, with
        the model state after ``opt_state`` in and out for TGN/TPNet. It
        is pure, so a caller may step from states of its own (e.g. to
        compare two meshes from shared parameters). Single-device steps
        are ``jax.jit`` functions and can be ``.lower()``-ed."""
        return self._train_step

    def train_batches(self) -> Iterator[Dict[str, Any]]:
        """Yield the train split's batches as the train step consumes them
        (hooks run, prefetched and staged on the device), continuing from
        the current hook state; call ``reset_epoch_state`` first for a
        fresh epoch. Closing the generator stops the prefetch thread."""
        with self.manager.activate(TRAIN_KEY), contextlib.closing(
                iter(self._loader(self.train_data))) as batches:
            for batch in batches:
                yield self._batch_tensors(batch)

    def train_epoch(self) -> Tuple[float, float]:
        """One epoch over the train split. Returns (mean loss, seconds)."""
        tel = self.telemetry
        with tel.span("ctdg/epoch", model=self.model_name) as sp:
            self.reset_epoch_state()
            t0 = time.perf_counter()
            losses = []
            for bt in self.train_batches():
                # The jitted step is async: the span bounds Python and
                # dispatch; device time shows up as the next batch's
                # ``loader/wait`` (see docs/observability.md).
                with tel.span("ctdg/dispatch"):
                    if self.model_name in CTDG_STATELESS:
                        self.params, self.opt_state, loss = \
                            self._train_step(
                                self.params, self.opt_state, bt)
                    else:
                        (self.params, self.opt_state, self.model_state,
                         loss) = self._train_step(
                            self.params, self.opt_state,
                            self.model_state, bt)
                losses.append(loss)
            losses = [float(l) for l in losses]
            mean, secs = float(np.mean(losses)), time.perf_counter() - t0
            sp["loss"], sp["steps"] = mean, len(losses)
        return mean, secs

    def evaluate(self, split: str = "val") -> Tuple[float, float]:
        """One-vs-many MRR on val/test (warm state from train[, val])."""
        tel = self.telemetry
        with tel.span("ctdg/eval", split=split) as sp:
            self.reset_epoch_state()
            # Warm samplers/state through earlier splits w/o predicting.
            with tel.span("ctdg/warm"), self.manager.activate(TRAIN_KEY):
                warm = [self.train_data] + (
                    [self.val_data] if split == "test" else [])
                for d in warm:
                    for batch in self._loader(d):
                        bt = self._batch_tensors(batch)
                        if self.model_name in CTDG_STATEFUL:
                            _, self.model_state = self._eval_step(
                                self.params, self.model_state, bt
                            )
            data = self.val_data if split == "val" else self.test_data
            t0 = time.perf_counter()
            rrs, masks = [], []
            with self.manager.activate(EVAL_KEY):
                for batch in self._loader(data):
                    bt = self._batch_tensors(batch)
                    with tel.span("ctdg/eval_dispatch"):
                        if self.model_name in CTDG_STATELESS:
                            pos, neg = self._eval_step(self.params, bt)
                        else:
                            (pos, neg), self.model_state = self._eval_step(
                                self.params, self.model_state, bt
                            )
                    w = float(bt["batch_mask"].sum())
                    rrs.append(mrr(pos, neg, bt["batch_mask"]) * w)
                    masks.append(w)
            out = float(np.sum(rrs) / max(np.sum(masks), 1.0))
            sp["mrr"] = out
        return out, time.perf_counter() - t0


# ----------------------------------------------------------------------
# Shared snapshot-pair plumbing (DTDG link + node pipelines)
# ----------------------------------------------------------------------
class SnapshotPairPipeline:
    """Shared base of the scan-compiled snapshot pipelines.

    Owns the plumbing every snapshot-pair task repeats: tensorizing the
    stream into a ``SnapshotTensor``, mapping chronological ``DGData.split``
    boundaries onto snapshot rows (a prediction pair ``p -> p+1`` belongs
    to the split containing its *predicted* snapshot ``p+1``), the
    ``_split_pairs`` ranges, and the FIFO-bounded scan-input cache.
    Subclasses (``DTDGLinkPipeline``, ``train.nodeprop.DTDGNodePipeline``)
    add their task's extra scan inputs and bodies on top.
    """

    # Scan inputs are pure functions of (snapshot tensor, task inputs);
    # cache the few ranges an epoch reuses, FIFO-evicting beyond this bound
    # so long-lived pipelines don't accumulate per-chunk device copies.
    _XS_CACHE_MAX = 8

    def _init_snapshots(self, data: DGData, unit, capacity, device,
                        val_ratio: float, test_ratio: float) -> None:
        """Tensorize ``data`` once and map split times to snapshot rows."""
        self.snapshots = snapshot_tensor(data, unit, capacity=capacity,
                                         device=device)
        self.capacity = self.snapshots.capacity
        T = self.snapshots.num_snapshots
        train_d, val_d, test_d = data.split(val_ratio, test_ratio)
        test_row = (
            self.snapshots.row_of_time(int(test_d.edge_t[0]))
            if test_d.num_edge_events else T
        )
        # An empty val split collapses onto the test boundary (val pairs
        # empty, test pairs intact) rather than swallowing the test split.
        val_row = (
            self.snapshots.row_of_time(int(val_d.edge_t[0]))
            if val_d.num_edge_events else test_row
        )
        self.set_split_rows(val_row, test_row)
        self._xs_cache: Dict[Tuple, Dict[str, Any]] = {}

    def set_split_rows(self, val_row: int, test_row: int) -> None:
        """Install (clamped) snapshot-row split boundaries — the first val
        row and the first test row. ``val_row == test_row`` means no val
        pairs (e.g. the legacy ``train_frac`` mapping)."""
        T = self.snapshots.num_snapshots
        self._val_row = min(max(val_row, 1), T)
        self._test_row = min(max(test_row, self._val_row), T)

    def _split_pairs(self, split: str) -> Tuple[int, int]:
        """Prediction-pair range ``[lo, hi)`` for a split."""
        T = self.snapshots.num_snapshots
        if split == "train":
            return 0, max(self._val_row - 1, 0)
        if split == "val":
            return max(self._val_row - 1, 0), max(self._test_row - 1, 0)
        if split == "test":
            return max(self._test_row - 1, 0), max(T - 1, 0)
        raise ValueError(f"unknown split {split!r}")

    def _pair_slices(self, lo: int, hi: int) -> Dict[str, Any]:
        """The stacked current/predicted snapshot arrays for pairs
        ``[lo, hi)`` (pair p = snapshot p -> p+1) — the scan inputs every
        snapshot-pair task shares."""
        st = self.snapshots
        return {
            "src": st.src[lo:hi], "dst": st.dst[lo:hi],
            "mask": st.mask[lo:hi],
            "nsrc": st.src[lo + 1:hi + 1], "ndst": st.dst[lo + 1:hi + 1],
            "nmask": st.mask[lo + 1:hi + 1],
        }

    def _xs_cached(self, key: Tuple, build) -> Dict[str, Any]:
        """FIFO-bounded memoization of a scan-input dict keyed by ``key``."""
        if key not in self._xs_cache:
            if len(self._xs_cache) >= self._XS_CACHE_MAX:
                self._xs_cache.pop(next(iter(self._xs_cache)))
            self._xs_cache[key] = build()
        return self._xs_cache[key]


# ----------------------------------------------------------------------
# DTDG link prediction: scan-compiled snapshot pipeline
# ----------------------------------------------------------------------
class DTDGLinkPipeline(SnapshotPairPipeline):
    """DTDG link prediction over the scan-compiled snapshot pipeline.

    Snapshot t's embeddings predict the edges of snapshot t+1. The stream is
    tensorized once into a device-resident ``SnapshotTensor``; with
    ``compiled=True`` (default) each split's epoch is one scanned jitted
    call (optionally chunked via ``chunk_size``), with ``compiled=False``
    the same body runs as a per-snapshot jitted loop through the
    ``RECIPE_DTDG_SNAPSHOT`` hook pipeline — the scan-vs-loop parity oracle.

    Splits are chronological ``DGData.split`` boundaries mapped to snapshot
    rows; a prediction pair belongs to the split that contains its
    *predicted* snapshot, and the recurrent state is carried across split
    boundaries by advance-only scans. Checkpoints bundle
    ``{params, opt_state[, model_state], hooks, pipeline}`` where
    ``pipeline`` holds the mid-epoch snapshot-pair cursor. See
    ``docs/dtdg.md`` for the full pipeline.
    """

    def __init__(
        self,
        model_name: str,
        data: DGData,
        snapshot_unit: TimeDelta | str = "h",
        d_embed: int = 128,
        lr: Optional[float] = None,
        num_negatives: int = 1,
        eval_negatives: int = 20,
        edge_capacity: Optional[int] = None,
        seed: int = 0,
        val_ratio: float = 0.15,
        test_ratio: float = 0.15,
        compiled: bool = True,
        chunk_size: Optional[int] = None,
        device=None,
        telemetry: Optional[Telemetry] = None,
    ):
        if model_name not in snapshot.SNAPSHOT_MODELS:
            raise ValueError(f"unknown DTDG model {model_name!r}")
        self.model_name = model_name
        self.data = data
        # Fresh instance (not the NULL singleton) so TrainLoop's history
        # sink never leaks onto unrelated pipelines.
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self.unit = TimeDelta.coerce(snapshot_unit)
        self.num_negatives = num_negatives
        self.eval_negatives = eval_negatives
        self._seed = seed
        self.compiled = compiled
        self.chunk_size = chunk_size

        # Tensorize once (jitted discretize + scatter; core/loader.py) and
        # map the chronological split boundaries to snapshot rows.
        self._init_snapshots(data, self.unit, edge_capacity, device,
                             val_ratio, test_ratio)

        self.cfg = snapshot.SnapshotConfig(num_nodes=data.num_nodes, d_embed=d_embed)
        self.params = snapshot.init_params(
            model_name, jax.random.PRNGKey(seed), self.cfg
        )
        self._apply = snapshot.make_apply(model_name, self.cfg)
        self._has_state = model_name != "gcn"
        self.model_state = snapshot.init_state(model_name, self.cfg)

        self.manager = RecipeRegistry.build(
            RECIPE_DTDG_SNAPSHOT,
            num_nodes=data.num_nodes,
            capacity=self.capacity,
            num_negatives=num_negatives,
            eval_negatives=eval_negatives,
            seed=seed,
            device=device,
        )
        self.manager.telemetry = self.telemetry

        self.opt_cfg = AdamWConfig(lr=1e-3 if lr is None else lr)
        self.opt_state = adamw_init(self.params)
        self._cursor = 0  # next train pair (mid-epoch checkpoint resume)
        self._build_steps()

    # ------------------------------------------------------------------
    def _build_steps(self):
        apply = self._apply
        opt_cfg = self.opt_cfg

        def loss_fn(params, state, x):
            z, new_state = apply(params, x["src"], x["dst"], x["mask"], state)
            h_src = z[x["nsrc"]]
            pos = link_decoder(params["decoder"], h_src, z[x["ndst"]])
            neg = link_decoder(params["decoder"], h_src, z[x["neg"]])
            return bce_link_loss(pos, neg, x["nmask"]), new_state

        def train_body(carry, x):
            params, opt_state, state = carry
            (loss, new_state), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, state, x
            )
            params, opt_state = adamw_update(params, grads, opt_state, opt_cfg)
            return (params, opt_state, new_state), loss

        def eval_body(params, state, x):
            z, new_state = apply(params, x["src"], x["dst"], x["mask"], state)
            h_src = z[x["nsrc"]]
            pos = link_decoder(params["decoder"], h_src, z[x["ndst"]])
            neg = link_decoder(params["decoder"], h_src, z[x["neg"]])
            return new_state, (pos, neg)

        def advance_body(params, state, x):
            _, new_state = apply(params, x["src"], x["dst"], x["mask"], state)
            return new_state

        # One jitted scan per split chunk (the compiled pipeline) and the
        # same bodies as standalone jitted per-snapshot steps (loop mode).
        self._train_scan = jax.jit(
            lambda p, o, s, xs: jax.lax.scan(train_body, (p, o, s), xs)
        )
        self._train_step = jax.jit(lambda p, o, s, x: train_body((p, o, s), x))
        self._eval_scan = jax.jit(
            lambda p, s, xs: jax.lax.scan(
                lambda st, x: eval_body(p, st, x), s, xs
            )
        )
        self._eval_step = jax.jit(eval_body)
        self._advance_scan = jax.jit(
            lambda p, s, xs: jax.lax.scan(
                lambda st, x: (advance_body(p, st, x), None), s, xs
            )[0]
        )
        self._advance_step = jax.jit(advance_body)

    # ------------------------------------------------------------------
    def _pair_xs(self, lo: int, hi: int, m: int) -> Dict[str, Any]:
        """Stacked scan inputs for prediction pairs ``[lo, hi)`` (pair p =
        snapshot p -> p+1) with ``m`` negatives per predicted edge."""
        def build():
            rows = np.arange(lo + 1, hi + 1)
            return {**self._pair_slices(lo, hi),
                    "neg": self.snapshots.negatives(self._seed, m, rows)}

        return self._xs_cached((lo, hi, m), build)

    def _pair_x(self, p: int, neg) -> Dict[str, Any]:
        """One pair's arrays (loop mode), with hook-produced negatives."""
        st = self.snapshots
        return {
            "src": st.src[p], "dst": st.dst[p], "mask": st.mask[p],
            "nsrc": st.src[p + 1], "ndst": st.dst[p + 1],
            "nmask": st.mask[p + 1], "neg": neg,
        }

    def _hook_negatives(self, p: int):
        """Run the predicted snapshot through the active hook pipeline and
        return its ``neg`` draws (identical to the scan path's bulk draw)."""
        from repro.core.batch import Batch

        st = self.snapshots
        batch = Batch(
            {"src": st.src[p + 1], "dst": st.dst[p + 1],
             "time": np.full(st.capacity, (st.t0 + p + 1) * st.ticks,
                             dtype=np.int64),
             "snap_mask": st.mask[p + 1]},
            meta={"snapshot_row": p + 1},
        )
        return self.manager.execute(batch)["neg"]

    def _chunks(self, lo: int, hi: int):
        step = self.chunk_size or max(hi - lo, 1)
        for start in range(lo, hi, step):
            yield start, min(start + step, hi)

    def reset_epoch_state(self):
        """Reset hook cursors and the recurrent state (start of an epoch)."""
        self.manager.reset_state()
        self.model_state = snapshot.init_state(self.model_name, self.cfg)

    @property
    def snapshot_cursor(self) -> int:
        """Next train snapshot pair to run — the mid-epoch resume cursor
        carried in checkpoints as ``pipeline/snapshot_cursor``."""
        return self._cursor

    # ------------------------------------------------------------------
    def train_chunk(self) -> Optional[list]:
        """Run ONE compiled chunk from the current snapshot cursor.

        The kill/resume granule of the scan pipeline: each call scans the
        next ``chunk_size`` snapshot pairs, advances ``_cursor`` (the value
        checkpointed as ``pipeline.snapshot_cursor``), and returns the
        chunk's per-pair losses. Returns ``None`` once the train split is
        exhausted (and zeroes the cursor so the next call starts a fresh
        epoch). A checkpoint written between calls restores to exactly this
        boundary, which is what makes mid-epoch kill + resume bit-identical
        to an uninterrupted run. Compiled mode only."""
        if not self.compiled:
            raise RuntimeError("train_chunk requires compiled=True")
        lo, hi = self._split_pairs("train")
        start = max(self._cursor, lo)
        if start >= hi:
            self._cursor = 0
            return None
        if self._cursor == 0:
            self.reset_epoch_state()
        chi = min(start + (self.chunk_size or max(hi - lo, 1)), hi)
        with self.telemetry.span("dtdg/chunk", lo=start, hi=chi):
            xs = self._pair_xs(start, chi, self.num_negatives)
            (self.params, self.opt_state, self.model_state), ls = \
                self._train_scan(self.params, self.opt_state,
                                 self.model_state, xs)
        self._cursor = chi
        return [float(l) for l in np.asarray(ls)]

    def lower_train_chunk(self):
        """The first train chunk's scan (the whole split by default), as
        ``train_chunk`` runs it, lowered from the current state
        (``jax.stages.Lowered``): to see what an epoch compiles to."""
        lo, hi = self._split_pairs("train")
        chi = min(lo + (self.chunk_size or max(hi - lo, 1)), hi)
        return self._train_scan.lower(
            self.params, self.opt_state, self.model_state,
            self._pair_xs(lo, chi, self.num_negatives))

    def train_epoch(self) -> Tuple[float, float]:
        """One epoch over the train split. Returns (mean loss, seconds).

        ``compiled=True``: one scanned jitted call per chunk (default: the
        whole split in one call). A restored mid-epoch snapshot cursor
        resumes from where the checkpoint left off.
        """
        tel = self.telemetry
        with tel.span("dtdg/epoch", model=self.model_name,
                      compiled=self.compiled) as sp:
            lo, hi = self._split_pairs("train")
            if self._cursor == 0:
                self.reset_epoch_state()
            start = max(self._cursor, lo)
            t0 = time.perf_counter()
            losses = []
            if self.compiled:
                while True:
                    chunk_losses = self.train_chunk()
                    if chunk_losses is None:
                        break
                    losses.extend(chunk_losses)
            else:
                with self.manager.activate(TRAIN_KEY):
                    for p in range(start, hi):
                        x = self._pair_x(p, self._hook_negatives(p))
                        with tel.span("dtdg/step"):
                            (self.params, self.opt_state,
                             self.model_state), loss = self._train_step(
                                self.params, self.opt_state,
                                self.model_state, x)
                        losses.append(float(loss))
                        self._cursor = p + 1
            self._cursor = 0
            secs = time.perf_counter() - t0
            mean = float(np.mean(losses)) if losses else 0.0
            sp["loss"], sp["pairs"] = mean, len(losses)
        return mean, secs

    def evaluate(self, split: str = "val") -> Tuple[float, float]:
        """One-vs-many MRR on val/test. Returns (MRR, seconds).

        The recurrent state is warmed through all earlier snapshots with an
        advance-only scan (carried across the split boundary), then the
        split's pairs are scored in one scanned call per chunk.
        """
        tel = self.telemetry
        with tel.span("dtdg/eval", split=split) as sp:
            lo, hi = self._split_pairs(split)
            self.manager.reset_state()
            t0 = time.perf_counter()
            # Local state: evaluation re-warms from scratch and must not
            # clobber a mid-epoch training state (checkpoint-resume safety).
            state = snapshot.init_state(self.model_name, self.cfg)
            if self._has_state and lo > 0:
                if self.compiled:
                    st = self.snapshots
                    warm = {"src": st.src[:lo], "dst": st.dst[:lo],
                            "mask": st.mask[:lo]}
                    state = self._advance_scan(self.params, state, warm)
                else:
                    st = self.snapshots
                    for p in range(lo):
                        state = self._advance_step(
                            self.params, state,
                            {"src": st.src[p], "dst": st.dst[p],
                             "mask": st.mask[p]},
                        )
            pos_rows, neg_rows, mask_rows = [], [], []
            if self.compiled:
                for clo, chi in self._chunks(lo, hi):
                    xs = self._pair_xs(clo, chi, self.eval_negatives)
                    state, (pos, neg) = self._eval_scan(self.params, state,
                                                        xs)
                    pos_rows.extend(np.asarray(pos))
                    neg_rows.extend(np.asarray(neg))
                    mask_rows.extend(np.asarray(xs["nmask"]))
            else:
                with self.manager.activate(EVAL_KEY):
                    for p in range(lo, hi):
                        x = self._pair_x(p, self._hook_negatives(p))
                        state, (pos, neg) = self._eval_step(self.params,
                                                            state, x)
                        pos_rows.append(np.asarray(pos))
                        neg_rows.append(np.asarray(neg))
                        mask_rows.append(np.asarray(x["nmask"]))
            out = weighted_mrr(pos_rows, neg_rows, mask_rows)
            sp["mrr"] = out
        return out, time.perf_counter() - t0

    # -- checkpointing ---------------------------------------------------
    # Same composable contract as CTDGLinkPipeline: params + optimizer
    # state + recurrent model state + hook cursors + the snapshot-pair
    # cursor, so a restored run resumes mid-epoch at the right snapshot
    # with the right negative draws.
    def _ckpt_tree(self) -> Dict[str, Any]:
        tree = {
            "params": self.params,
            "opt_state": self.opt_state,
            "hooks": self.manager.state_dict(),
            "pipeline": {"snapshot_cursor": np.int64(self._cursor)},
        }
        if self._has_state:
            tree["model_state"] = self.model_state
        return tree

    def save_checkpoint(self, ckpt_dir: str, step: int) -> str:
        """Write a checkpoint (atomic step directory). Returns its path."""
        return save_bundle(ckpt_dir, step, self._ckpt_tree(), self.model_name,
                           trainer="snapshot")

    def restore_checkpoint(self, ckpt_dir: str, step: Optional[int] = None) -> int:
        """Restore params/opt/model state, hook cursors and the snapshot
        cursor; returns the checkpoint step."""
        target = {k: v for k, v in self._ckpt_tree().items() if k != "hooks"}
        tree, step = restore_bundle(ckpt_dir, step, target, self.model_name)
        self.params = tree["params"]
        self.opt_state = tree["opt_state"]
        self.manager.load_state_dict(tree["hooks"])
        self._cursor = int(np.asarray(tree["pipeline"]["snapshot_cursor"]))
        if self._has_state:
            self.model_state = tree["model_state"]
        return step

    def run_epoch(self, train_frac: Optional[float] = None,
                  train: bool = True) -> Tuple[float, float]:
        """Legacy shim: ``train=True`` -> ``train_epoch()``; otherwise
        ``evaluate('val')``. ``train_frac`` is ignored — splits now come
        from ``DGData.split`` (chronological val/test ratios) — so an
        explicitly passed value warns loudly instead of silently changing
        which snapshots are scored."""
        if train_frac is not None:
            import warnings

            warnings.warn(
                "run_epoch(train_frac=...) is ignored; splits come from "
                "DGData.split — pass val_ratio/test_ratio to the pipeline "
                "and use train_epoch()/evaluate() instead",
                DeprecationWarning,
                stacklevel=2,
            )
        if train:
            return self.train_epoch()
        return self.evaluate("val")
