"""Model weights from the run's seed, in the benchmark's own layout.

The layout (leaf names and shapes) is written from the models' equations,
by each reference model (``reference/<name>.py``), so the reference reads
every weight by what it means. The run installs the same tree in the
program and checks first that the program expects exactly these leaves.
Weights are made on the device, in float32, in one jitted call.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def dense(d_in: int, d_out: int, bias: bool = True) -> dict:
    p = {"w": (d_in, d_out)}
    if bias:
        p["b"] = (d_out,)
    return p


def mlp(dims) -> dict:
    return {f"layer_{i}": dense(dims[i], dims[i + 1])
            for i in range(len(dims) - 1)}


def attention(d_q: int, d_kv: int, d: int) -> dict:
    return {"q": dense(d_q, d), "k": dense(d_kv, d), "v": dense(d_kv, d),
            "o": dense(d, d)}


def base_layout(d: int, d_time: int, num_nodes: int) -> dict:
    """The leaves every link model here has: node embeddings, the time
    encoding and the link decoder."""
    return {
        "nodes": {"emb": (num_nodes, d)},
        "time": {"w": (d_time,), "b": (d_time,)},
        "decoder": {"mlp": mlp([2 * d, d, 1])},
    }


def layout(model: dict, num_nodes: int, d_edge: int) -> dict:
    """Leaf shapes of ``model`` (the config's ``model`` entry), from the
    reference model of that name (``reference/<name>.py``)."""
    from .reference import check

    return check.model(model["name"]).layout(model["kwargs"], num_nodes,
                                             d_edge)


def _init(path: str, shape, key):
    """Node embeddings N(0, 0.02); time frequencies and phases N(0, 0.1)
    (TGAT's Bochner encoding); matrices N(0, 2 / (fan_in + fan_out));
    a norm's gain (a leaf named ``scale``) 1 + N(0, 0.02), near the ones
    a model starts from, so every branch behind a norm keeps its size;
    other vectors (biases) N(0, 0.02)."""
    x = jax.random.normal(key, shape, jnp.float32)
    if path.rsplit(".", 1)[-1] == "scale":
        return 1.0 + x * 0.02
    if path.endswith("emb"):
        return x * 0.02
    if path.startswith("time"):
        return x * 0.1
    if len(shape) == 2:
        return x * math.sqrt(2.0 / (shape[0] + shape[1]))
    return x * 0.02


def make(seed: int, shapes: dict):
    """The weight tree for ``shapes`` (from ``layout``), drawn from
    ``seed`` (any non-negative integer) on the default device."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    paths = [jax.tree_util.keystr(p, simple=True, separator=".")
             for p, _ in leaves]
    dims = [s for _, s in leaves]

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(dims))
        return [_init(p, s, k) for p, s, k in zip(paths, dims, keys)]

    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return jax.tree_util.tree_unflatten(treedef, build(key))


def same_layout(params, shapes) -> str | None:
    """None if ``params`` has exactly the leaves of ``shapes``, else what
    differs."""
    got = {jax.tree_util.keystr(p, simple=True, separator="."): tuple(x.shape)
           for p, x in jax.tree_util.tree_flatten_with_path(params)[0]}
    want = {jax.tree_util.keystr(p, simple=True, separator="."): s
            for p, s in jax.tree_util.tree_flatten_with_path(
                shapes, is_leaf=lambda x: isinstance(x, tuple))[0]}
    if got == want:
        return None
    diff = sorted(set(got.items()) ^ set(want.items()))
    return f"program and benchmark weight layouts differ: {diff[:6]}"
