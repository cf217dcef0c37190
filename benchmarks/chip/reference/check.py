"""What the plain reference says a run should have produced.

Training: from the run's seed weights, the reference takes the same first
train batches (their events and negative destinations), finds each seed's
neighborhoods in the stream itself, and steps its own AdamW. It reports
each step's loss, the norm of each leaf of the first gradient, and the
norm of each leaf's change over the steps.

Evaluation: the scores of given batches, under the weights the run used.

Each model's equations are in ``reference/<name>.py``, found by the
configuration's model name. A model scores a link either from one
embedding per seed (``embed``, then the shared decoder) or, where the
score depends on the pair, by its own ``link_logits``.

``dtype`` float32 runs at the highest matmul precision (the reference);
bfloat16 is its control, the next precision down.
"""

from __future__ import annotations

import contextlib
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from . import layers
from .recency import Recency


def model(name: str):
    """The reference model ``name``: the module ``reference/<name>.py``,
    with ``HOPS``, ``layout``, ``init_state``, ``update``, and ``embed``
    or ``link_logits``.

    ``embed(params, heads, g, state, dtype, block=0)`` gives one row per
    seed ``[src | dst | negatives]``, scored by ``layers.link_logits``.
    A model whose score depends on the pair defines instead
    ``link_logits(params, heads, g, state, dtype, b, block=0)`` ->
    positive (B,) and negative (B, Nn) logits, from the same ``g``
    (``batch_inputs``: every seed's neighborhood); ``block`` > 0 bounds
    how many pairs it computes at once."""
    return importlib.import_module(f"{__package__}.{name}")


class Stream:
    """The event stream as the reference sees it."""

    def __init__(self, src, dst, t, edge_feats, k: int):
        self.src = np.asarray(src, np.int64)
        self.dst = np.asarray(dst, np.int64)
        self.t = np.asarray(t, np.int64)
        self.edge_feats = np.asarray(edge_feats, np.float32)
        self.recency = Recency(self.src, self.dst, self.t, k)
        self._tables = {}

    def edge_table(self, dtype):
        """The edge features on the device, at ``dtype``."""
        if dtype not in self._tables:
            self._tables[dtype] = jnp.asarray(self.edge_feats, dtype)
        return self._tables[dtype]

    def edge_rows(self, eids):
        return np.where((eids >= 0)[..., None],
                        self.edge_feats[np.maximum(eids, 0)], 0.0)

    def block(self, nodes, revealed: int):
        ids, times, eids, mask = self.recency.sample(nodes, revealed)
        return {"ids": ids, "times": times, "mask": mask, "eids": eids}


def batch_inputs(stream: Stream, batch: dict, hops: int, dtype):
    """Seeds ``[src | dst | negatives]`` of a batch (``lo``: its first
    event's index; ``src``/``dst``/``time``/``mask`` (B,); ``neg``
    (B, Nn)), their neighborhoods, and the batch's own events."""
    b, nn = batch["neg"].shape
    seeds = np.concatenate([batch["src"], batch["dst"],
                            batch["neg"].reshape(-1)]).astype(np.int64)
    times = np.concatenate([batch["time"], batch["time"],
                            np.repeat(batch["time"], nn)]).astype(np.int64)
    g = {"seeds": seeds, "times": times,
         "n1": stream.block(seeds, batch["lo"])}
    if hops == 2:
        g["n2"] = stream.block(g["n1"]["ids"].reshape(-1), batch["lo"])
    ev = {"src": batch["src"], "dst": batch["dst"], "time": batch["time"],
          "mask": batch["mask"],
          "e": np.where(batch["mask"][:, None], stream.edge_rows(
              np.where(batch["mask"], batch["lo"] + np.arange(b), -1)),
              0.0).astype(dtype)}
    g = jax.tree.map(jnp.asarray, g)
    g["edges"] = stream.edge_table(dtype)
    return g, jax.tree.map(jnp.asarray, ev)


def _precision(dtype):
    if dtype == jnp.float32:
        return jax.default_matmul_precision("highest")
    return contextlib.nullcontext()


def _adamw(params, grads, state, opt):
    step = state["step"] + 1
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32),
                      state["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(
        g.astype(jnp.float32)), state["nu"], grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step

    def move(p, m, v):
        d = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
        return (p - opt["lr"] * d).astype(p.dtype)

    return (jax.tree.map(move, params, mu, nu),
            {"mu": mu, "nu": nu, "step": step})


def leaf_norms(tree) -> dict:
    """``{leaf path: float64 L2 norm}``."""
    return {jax.tree_util.keystr(p, simple=True, separator="."):
            float(np.linalg.norm(np.asarray(x, np.float64)))
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def train(spec: dict, opt: dict, stream: Stream, batches, params,
          dtype=jnp.float32, num_nodes: int = 0) -> dict:
    """Step the reference through ``batches`` from ``params``."""
    kw = spec["kwargs"]
    heads, mod = kw["num_heads"], model(spec["name"])
    params = jax.tree.map(lambda x: jnp.asarray(x, dtype), params)
    start = params
    opt_state = {"mu": jax.tree.map(
                     lambda x: jnp.zeros(x.shape, jnp.float32), params),
                 "nu": jax.tree.map(
                     lambda x: jnp.zeros(x.shape, jnp.float32), params),
                 "step": 0}
    state = mod.init_state(kw, num_nodes, dtype)

    def loss_fn(p, g, ev, state):
        pos, neg = _logits(mod, p, heads, g, state, dtype,
                           ev["src"].shape[0])
        return layers.bce(pos, neg, ev["mask"])

    losses, first = [], None
    with _precision(dtype):
        grad_fn = jax.jit(jax.value_and_grad(loss_fn))
        update = jax.jit(lambda p, s, ev: mod.update(p, s, ev, dtype))
        for batch in batches:
            g, ev = batch_inputs(stream, batch, mod.HOPS, dtype)
            loss, grads = grad_fn(params, g, ev, state)
            state = update(params, state, ev)
            if first is None:
                first = leaf_norms(grads)
            losses.append(float(loss))
            params, opt_state = _adamw(params, grads, opt_state, opt)
    change = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                          - b.astype(jnp.float32), params, start)
    return {"losses": losses, "grad": first, "change": leaf_norms(change)}


def _logits(mod, params, heads: int, g, state, dtype, b: int,
            block: int = 0, jitted: bool = False):
    """Positive (B,) and negative (B, Nn) logits of the batch in ``g``:
    the model's own ``link_logits`` where it has one, else its ``embed``
    and the shared decoder. ``jitted``: through the model's functions
    compiled apart (eval); else traced in place (inside train's step)."""
    if hasattr(mod, "link_logits"):
        fn = _jitted(mod, "link_logits") if jitted else mod.link_logits
        return fn(params, heads=heads, g=g, state=state, dtype=dtype, b=b,
                  block=block)
    fn = _jitted(mod, "embed") if jitted else mod.embed
    h = fn(params, heads=heads, g=g, state=state, dtype=dtype, block=block)
    return layers.link_logits(params, h, b)


@functools.cache
def _jitted(mod, name: str):
    static = ("heads", "dtype", "block") + (("b",) if name == "link_logits"
                                            else ())
    return jax.jit(getattr(mod, name), static_argnames=static)


def eval_scores(spec: dict, stream: Stream, batch: dict, params,
                dtype=jnp.float32, block: int = 8800):
    """Positive (B,) and negative (B, Nn) scores of one eval batch of a
    model that keeps no state between batches."""
    mod = model(spec["name"])
    if mod.init_state(spec["kwargs"], 1, dtype) is not None:
        raise NotImplementedError(
            f"the eval reference of {spec['name']!r} would have to replay "
            "its state through the stream")
    heads = spec["kwargs"]["num_heads"]
    params = jax.tree.map(lambda x: jnp.asarray(x, dtype), params)
    g, _ = batch_inputs(stream, batch, mod.HOPS, dtype)
    with _precision(dtype):
        pos, neg = _logits(mod, params, heads, g, None, dtype,
                           batch["src"].shape[0], block, jitted=True)
    return np.asarray(pos, np.float64), np.asarray(neg, np.float64)


# Leaves whose reference gradient is under this share of the median
# leaf's move under Adam by rounding alone (a key's bias under softmax,
# TGN's memory updater, which no loss term reaches): their change is not
# compared.
STILL = 1e-3


def train_gaps(program: dict, ref: dict) -> dict:
    """The numbers a train cell's ``correct`` compares, each a relative
    gap between the program's reading and the reference's:

    ``loss_gap``: worst step's ``|loss - ref| / |ref|``;
    ``grad_gap``: worst leaf's gap of first-gradient norms, against the
    larger of the reference leaf's norm and the median leaf's;
    ``change_gap``: the same for the norm of each leaf's change over the
    checked steps, over the leaves the reference's gradient moves.
    """
    loss = max(abs(p - r) / max(abs(r), 1e-30)
               for p, r in zip(program["losses"], ref["losses"]))
    g_ref, g_prog = ref["grad"], program["grad"]
    med = float(np.median(list(g_ref.values())))
    grad = max(abs(g_prog[k] - v) / max(v, med, 1e-30)
               for k, v in g_ref.items())
    moved = [k for k, v in g_ref.items() if v >= STILL * med]
    c_ref, c_prog = ref["change"], program["change"]
    med_c = float(np.median([c_ref[k] for k in moved]))
    change = max(abs(c_prog[k] - c_ref[k]) / max(c_ref[k], med_c, 1e-30)
                 for k in moved)
    return {"loss_gap": loss, "grad_gap": grad, "change_gap": change}


def score_gap(program, ref, valid: int) -> float:
    """Widest ``|score - ref|`` over the first ``valid`` rows' positive
    and negative scores."""
    (pp, pn), (rp, rn) = program, ref
    return float(max(
        np.abs(np.asarray(pp, np.float64)[:valid] - rp[:valid]).max(
            initial=0.0),
        np.abs(np.asarray(pn, np.float64)[:valid] - rn[:valid]).max(
            initial=0.0)))
