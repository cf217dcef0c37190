"""Work of one step of a link model, from the model equations and the
batch's own ids. What differs by model is in ``roofline/<name>.py``.

``step_work`` returns the fused attention calls of the step (forward,
and backward when training), each as ``attention.Work``, none for a model
that runs no fused kernel (``FUSED = False`` in its module), and the step's
model operations: every product and sum the equations need for the valid
events of the batch, forward and backward for training (backward counted
as twice the forward), the memory update (forward only: no gradient flows
through it) and AdamW. The projection of all N nodes' K/V rows, which the
fused path does in place of per-slot projections, is an implementation
choice and is not counted; the per-slot projections the equations call for
are.
"""

from __future__ import annotations

import importlib
import types

import numpy as np

from . import attention as att

ADAMW_OPS = 12  # per parameter: two moments, two corrections, sqrt, step


def model(name: str):
    """The work counts of model ``name``: ``roofline/<name>.py``, with
    ``KEYS``, ``num_params``, ``forward`` and, optionally, ``FUSED``
    (default True: the batch's first hop is a fused attention call)."""
    return importlib.import_module(f"{__package__}.{name}")


def dense(n: float, d_in: int, d_out: int) -> float:
    return 2.0 * n * d_in * d_out + n * d_out


def mlp(n: float, dims) -> float:
    ops = sum(dense(n, dims[i], dims[i + 1]) for i in range(len(dims) - 1))
    return ops + n * sum(dims[1:-1])  # ReLUs


def layer(n_q: float, slots: float, d_q: int, d_kv: int, d: int,
          d_time: int, heads: int, d_merge_in: int) -> float:
    """One attention layer: query projection, per-slot time code and K/V
    projections, scores, softmax, weighted sum, output projection, merge
    MLP."""
    return (n_q * 3 * d_time
            + dense(n_q, d_q, d)
            + slots * (3 * d_time + 2 * dense(1, d_kv, d)
                       + 4 * d + 3 * heads)
            + dense(n_q, d, d)
            + mlp(n_q, [d_merge_in, d, d]))


def decoder(pairs: float, d: int) -> float:
    return mlp(pairs, [2 * d, d, 1]) + 10 * pairs  # + log-sigmoid loss


def step_work(name: str, kw: dict, k: int, d_edge: int, num_nodes: int,
              arrays: dict, train: bool):
    """``(kernel calls, model operations)`` of one step whose batch held
    ``arrays`` (host copies of ``model(name).KEYS``)."""
    seeds = np.asarray(arrays["seed_nodes"])
    mask = np.asarray(arrays["batch_mask"], bool)
    n_ev = mask.size
    n_neg = (seeds.size - 2 * n_ev) // n_ev
    # Seeds of padded events are not the model's work.
    valid_seed = np.concatenate([mask, mask, np.repeat(mask, n_neg)])
    q_ids = np.where(valid_seed, seeds, -1)
    n1 = (arrays["nbr_ids"], arrays["nbr_eids"], arrays["nbr_mask"])
    first = att.touched(q_ids, *n1)
    b = types.SimpleNamespace(
        mask=mask, valid_seed=valid_seed, q_ids=q_ids, n1=n1,
        n_q=float(valid_seed.sum()), slots1=float(first.slots),
        pairs=float(mask.sum()) * (1 + n_neg))
    mod = model(name)
    more, fwd, update = mod.forward(kw, d_edge, b, arrays)
    calls = [first] + more if getattr(mod, "FUSED", True) else []
    kernel = []
    if calls:
        dm = att.Dims(d=kw["d_model"], heads=kw["num_heads"],
                      d_time=kw["d_time"], d_edge=d_edge, k=k)
        kernel = [att.forward(dm, c) for c in calls]
        if train:
            kernel += [att.backward(dm, c) for c in calls]
    if not train:
        return kernel, fwd + update
    params = mod.num_params(kw, num_nodes, d_edge)
    return kernel, 3 * fwd + update + ADAMW_OPS * params
