"""Logical-axis sharding (MaxText-style rules) and mesh helpers.

Every parameter and key activation is annotated with *logical* axis names
("batch", "embed", "heads", ...). A rule table maps logical names to mesh
axes; GSPMD derives the collectives. Rules differ per parallelism profile
(pure TP, FSDP+TP, ...) and per mesh (single-pod vs multi-pod).

The active (mesh, rules) pair is process-global context set by the launcher;
model code calls ``shard(x, "batch", "seq", "embed")`` which is a no-op when
no mesh is active (CPU tests).

This module is also the home of the *node-partitioned sampler state* layout
shared by the device-resident temporal samplers (see ``docs/sharding.md``):

  * ``make_node_mesh`` — a 1-D mesh over the first N devices, axis "data";
  * ``node_rows_per_shard`` / ``row_sharding`` / ``replicated_sharding`` —
    the row-wise node-id partition arithmetic and the ``NamedSharding``s
    the samplers, hooks, and ``PrefetchLoader`` all agree on. The logical
    axis name for node-partitioned state is ``"nodes"`` (see
    ``DEFAULT_RULES``).
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisVal = Union[None, str, Tuple[str, ...]]
Rules = Dict[str, AxisVal]

# Default rules: DP over (pod, data); TP over model for heads/mlp/vocab/
# experts; FSDP (ZeRO-3) shards the embed axis of params over data.
DEFAULT_RULES: Rules = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "embed_fsdp": "data",  # param-only embed axis for FSDP sharding
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "qkv": "model",
    "mlp": "model",
    "moe_mlp": "model",
    "experts": None,
    "expert_cap": None,  # capacity axis of (E, C, d) expert batches
    "vocab": "model",
    "layers": None,
    "state": None,
    "conv": None,
    "frames": None,
    "patches": None,
    "cache_seq": None,
    "seq_shard": ("pod", "data"),  # sequence parallelism for long-context
    "nodes": "data",  # node-id row partition of device sampler state
}


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: Rules = dict(DEFAULT_RULES)


_CTX = _Ctx()


def set_sharding_context(mesh: Optional[Mesh], rules: Optional[Rules] = None) -> None:
    """Install the process-global (mesh, rules) pair used by ``shard``."""
    _CTX.mesh = mesh
    _CTX.rules = dict(DEFAULT_RULES if rules is None else rules)


def get_mesh() -> Optional[Mesh]:
    """The active mesh set by ``set_sharding_context`` (None = no mesh)."""
    return _CTX.mesh


def get_rules() -> Rules:
    """The active logical-axis rule table."""
    return _CTX.rules


class sharding_context:
    """``with sharding_context(mesh, rules): ...``"""

    def __init__(self, mesh: Optional[Mesh], rules: Optional[Rules] = None):
        self._new = (mesh, rules)
        self._old: Tuple[Optional[Mesh], Rules] = (None, {})

    def __enter__(self):
        self._old = (_CTX.mesh, _CTX.rules)
        set_sharding_context(*self._new)
        return self

    def __exit__(self, *exc):
        _CTX.mesh, _CTX.rules = self._old


def _axis_size(mesh: Mesh, ax: str) -> int:
    return mesh.shape[ax]


def _mesh_axes_for(logical: Sequence[Optional[str]], rules: Rules, mesh: Mesh,
                   shape: Optional[Sequence[int]] = None):
    """Map logical axis names to mesh axes.

    Rules whose mesh axis does not exist on this mesh (e.g. 'pod' on the
    single-pod mesh) are dropped. When ``shape`` is given, mappings that do
    not evenly divide the dimension are reduced (dropping axes from the
    front of a tuple mapping) or dropped — JAX/GSPMD requires even tiling.
    """
    out = []
    for i, name in enumerate(logical):
        if name is None:
            out.append(None)
            continue
        ax = rules.get(name)
        if ax is None:
            out.append(None)
            continue
        if isinstance(ax, str):
            ax = (ax,)
        live = tuple(a for a in ax if a in mesh.axis_names)
        if shape is not None:
            dim = shape[i]
            # reduce the mapping until its product divides the dim
            while live:
                prod = int(np.prod([_axis_size(mesh, a) for a in live]))
                if prod and dim % prod == 0:
                    break
                live = live[1:]
        out.append(live if len(live) > 1 else (live[0] if live else None))
    return out


def logical_spec(logical: Sequence[Optional[str]],
                 rules: Optional[Rules] = None,
                 mesh: Optional[Mesh] = None,
                 shape: Optional[Sequence[int]] = None) -> P:
    """``PartitionSpec`` for logical axis names under (rules, mesh);
    divisibility-reduced against ``shape`` when given."""
    mesh = mesh or _CTX.mesh
    rules = rules or _CTX.rules
    if mesh is None:
        return P()
    return P(*_mesh_axes_for(logical, rules, mesh, shape))


def logical_sharding(logical: Sequence[Optional[str]],
                     rules: Optional[Rules] = None,
                     mesh: Optional[Mesh] = None,
                     shape: Optional[Sequence[int]] = None) -> Optional[NamedSharding]:
    """``NamedSharding`` for logical axis names (None without a mesh)."""
    mesh = mesh or _CTX.mesh
    if mesh is None:
        return None
    return NamedSharding(mesh, logical_spec(logical, rules, mesh, shape))


def shard(x, *logical: Optional[str]):
    """Activation sharding constraint by logical axis names. No-op without
    an active mesh; divisibility-checked against ``x.shape``."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, logical_spec(logical, shape=x.shape))
    )


# ----------------------------------------------------------------------
# Node-partitioned sampler state (the ``docs/sharding.md`` layout)
# ----------------------------------------------------------------------
def make_node_mesh(shards: int, axis: str = "data",
                   devices: Optional[Sequence] = None) -> Mesh:
    """A 1-D mesh over the first ``shards`` devices.

    This is the mesh the device-resident samplers shard their node-row
    state over (``SamplerSpec.shards`` resolves through here). ``axis``
    defaults to ``"data"`` — the same axis the DP trainer shards event
    batches over, so sampler state and batch shards can share one mesh.
    """
    devices = list(devices if devices is not None else jax.devices())
    if shards < 1:
        raise ValueError("shards must be >= 1")
    if shards > len(devices):
        raise ValueError(
            f"requested {shards} sampler shards but only {len(devices)} "
            f"devices are visible (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N to emulate more)"
        )
    return Mesh(np.asarray(devices[:shards]), (axis,))


def make_2d_mesh(data_shards: int, node_shards: int,
                 axes: Tuple[str, str] = ("data", "nodes"),
                 devices: Optional[Sequence] = None) -> Mesh:
    """A 2-D ``(data, nodes)`` mesh over the first ``data*nodes`` devices.

    The data axis shards event batches (contiguous time-ordered
    sub-streams, DistTGL-style); the node axis shards sampler buffers /
    CSR adjacency row-wise by node id. Sampler state uses
    ``P(axes[1])`` placements (sharded over nodes, replicated over data);
    batch tensors inside the 2-D train step use ``P(axes[0])``.
    """
    if data_shards < 1 or node_shards < 1:
        raise ValueError("mesh axis sizes must be >= 1")
    devices = list(devices if devices is not None else jax.devices())
    need = data_shards * node_shards
    if need > len(devices):
        raise ValueError(
            f"requested a {data_shards}x{node_shards} mesh but only "
            f"{len(devices)} devices are visible (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N to emulate more)"
        )
    grid = np.asarray(devices[:need]).reshape(data_shards, node_shards)
    return Mesh(grid, axes)


def sync_state_masked_psum(state: Dict, touched, axis: str) -> Dict:
    """DistTGL-style masked-psum model-state sync inside ``shard_map``.

    ``touched`` is a bool mask over state rows (leading dim of every value
    in ``state``): rows touched on exactly one shard of ``axis`` take that
    shard's value; rows touched on several take the mean; untouched rows
    keep their (replicated) local value. Staleness is bounded by one batch
    — the DistTGL trade-off documented in ``distributed/dp_trainer.py``.
    """
    cnt = jax.lax.psum(touched.astype(jnp.float32), axis)
    out = {}
    for key, val in state.items():
        m = touched
        while m.ndim < val.ndim:
            m = m[..., None]
        contrib = jnp.where(m, val, 0.0).astype(jnp.float32)
        summed = jax.lax.psum(contrib, axis)
        c = jnp.maximum(cnt, 1.0)
        while c.ndim < val.ndim:
            c = c[..., None]
        mean = summed / c
        keep = cnt > 0
        while keep.ndim < val.ndim:
            keep = keep[..., None]
        out[key] = jnp.where(keep, mean, val.astype(jnp.float32)).astype(val.dtype)
    return out


def node_rows_per_shard(num_nodes: int, shards: int) -> int:
    """Node rows owned by each shard under the row-wise node-id partition:
    ``ceil(num_nodes / shards)`` (the last shard may own padding rows)."""
    return max(-(-int(num_nodes) // int(shards)), 1)


def row_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """``NamedSharding`` splitting an array's leading (row) dimension over
    ``axis`` — the placement of node-partitioned sampler state."""
    return NamedSharding(mesh, P(axis))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully-replicated ``NamedSharding`` over ``mesh`` — the placement of
    per-batch tensors feeding sharded sampler computations."""
    return NamedSharding(mesh, P())
