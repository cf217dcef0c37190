"""A link model whose score depends on the pair, and one that runs no
fused kernel, go through the benchmark's reference and work counts from
their own modules.

The test model scores ``(u, v)`` from each node's embedding plus the mean
of its recency neighbors' embeddings, with the count of neighbor pairs
that the two blocks share added to the source row, under a layer norm,
before the link decoder. It is registered as ``chip.reference.pair_toy``
and ``chip.roofline.pair_toy`` for the tests alone.
"""

from __future__ import annotations

import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip import weights
from chip.reference import check, layers
from chip.roofline import steps
from chip.test_chip_unmoved import (D_EDGE, K, NODES, OPT, cell_batch,
                                    tiny_batches, tiny_stream)

NAME = "pair_toy"
KW = {"d_model": 6, "num_heads": 1}
SPEC = {"name": NAME, "kwargs": KW}


def _layout(kw, num_nodes, d_edge):
    d = kw["d_model"]
    return {"nodes": {"emb": (num_nodes, d)}, "cooc": weights.dense(1, d),
            "norm": {"scale": (d,), "bias": (d,)},
            "decoder": {"mlp": weights.mlp([2 * d, d, 1])}}


def _norm(p, x):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * p["scale"] + p["bias"]


def _rows(params, g, idx):
    """(P, d) node rows of seeds ``idx``: embedding plus neighbor mean."""
    emb, n1 = params["nodes"]["emb"], g["n1"]
    ids, mask = n1["ids"][idx], n1["mask"][idx]
    nbr = (layers.rows(emb, ids) * mask[..., None]).sum(1)
    count = jnp.maximum(mask.sum(1, keepdims=True), 1)
    return layers.rows(emb, g["seeds"][idx]) + nbr / count


def _pairs(params, g, u, v, dtype):
    n1 = g["n1"]
    shared = ((n1["ids"][u][:, :, None] == n1["ids"][v][:, None, :])
              & n1["mask"][u][:, :, None] & n1["mask"][v][:, None, :])
    c = shared.sum((1, 2)).astype(dtype)[:, None]
    h_u = _norm(params["norm"],
                _rows(params, g, u) + layers.dense(params["cooc"], c))
    x = jnp.concatenate([h_u, _rows(params, g, v)], -1)
    return layers.mlp(params["decoder"]["mlp"], x)[:, 0]


def _link_logits(params, heads, g, state, dtype, b, block=0):
    s = g["seeds"].shape[0]
    nn = (s - 2 * b) // b
    u = np.concatenate([np.arange(b), np.repeat(np.arange(b), nn)])
    v = np.concatenate([np.arange(b, 2 * b), np.arange(2 * b, s)])
    step = block or u.size
    out = jnp.concatenate([_pairs(params, g, u[lo:lo + step],
                                  v[lo:lo + step], dtype)
                           for lo in range(0, u.size, step)])
    return out[:b], out[b:].reshape(b, nn)


def _forward(kw, d_edge, b, arrays):
    d = kw["d_model"]
    return [], b.n_q * d + b.slots1 * 2 * d + b.pairs * (4 * d + 1), 0.0


REF = types.ModuleType(f"chip.reference.{NAME}")
REF.HOPS = 1
REF.layout = _layout
REF.init_state = lambda kw, num_nodes, dtype: None
REF.update = lambda params, state, ev, dtype: state
REF.link_logits = _link_logits

ROOF = types.ModuleType(f"chip.roofline.{NAME}")
ROOF.KEYS = ("seed_nodes", "batch_mask", "nbr_ids", "nbr_eids", "nbr_mask")
ROOF.FUSED = False
ROOF.num_params = lambda kw, num_nodes, d_edge: 1000
ROOF.forward = _forward


@pytest.fixture
def pair_model(monkeypatch):
    monkeypatch.setitem(sys.modules, REF.__name__, REF)
    monkeypatch.setitem(sys.modules, ROOF.__name__, ROOF)


# The hand-written loop: one pair at a time, each node's K latest
# neighbors read off the stream itself.


def _neighbors(src, dst, node, revealed):
    seen = []
    for e in range(revealed):
        seen += [int(dst[e])] if src[e] == node else []
        seen += [int(src[e])] if dst[e] == node else []
    return seen[-K:]


def _one(params, u, nu, v, nv):
    emb = params["nodes"]["emb"]

    def row(x, nbrs):
        mean = sum((emb[n] for n in nbrs), jnp.zeros(emb.shape[1]))
        return emb[x] + mean / max(len(nbrs), 1)

    shared = float(sum(a == c for a in nu for c in nv))
    h_u = _norm(params["norm"], row(u, nu) + shared * params["cooc"]["w"][0]
                + params["cooc"]["b"])
    p = params["decoder"]["mlp"]
    x = jnp.concatenate([h_u, row(v, nv)])
    hid = jax.nn.relu(x @ p["layer_0"]["w"] + p["layer_0"]["b"])
    return (hid @ p["layer_1"]["w"] + p["layer_1"]["b"])[0]


def _loop(params, src, dst, batch):
    """Positive (B,) and negative (B, Nn) scores, pair by pair."""
    lo = batch["lo"]
    nbrs = {}

    def nb(x):
        if x not in nbrs:
            nbrs[x] = _neighbors(src, dst, x, lo)
        return nbrs[x]

    pos, neg = [], []
    for i in range(batch["src"].size):
        u, v = int(batch["src"][i]), int(batch["dst"][i])
        pos.append(_one(params, u, nb(u), v, nb(v)))
        neg.append(jnp.stack([_one(params, u, nb(u), int(w), nb(int(w)))
                              for w in batch["neg"][i]]))
    return jnp.stack(pos), jnp.stack(neg)


def _loop_loss(params, src, dst, batch):
    pos, neg = _loop(params, src, dst, batch)
    m = jnp.asarray(batch["mask"], jnp.float32)
    total = (-(jax.nn.log_sigmoid(pos) * m).sum()
             - (jax.nn.log_sigmoid(-neg) * m[:, None]).sum())
    return total / (m.sum() * (1 + neg.shape[1]))


def _params(seed=3):
    return weights.make(seed, weights.layout(SPEC, NODES, D_EDGE))


def _rel(got, want):
    return abs(got - want) / max(abs(want), 1e-30)


def test_train_matches_a_loop_over_pairs(pair_model):
    stream, src, dst, t = tiny_stream()
    batches = tiny_batches(src, dst, t, [250, 266], 16, 3, valid=12)
    params = _params()
    got = check.train(SPEC, OPT, stream, batches, params, num_nodes=NODES)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(_loop_loss)(params, src, dst,
                                                     batches[0])
    assert _rel(got["losses"][0], float(loss)) < 1e-6
    want = check.leaf_norms(grads)
    assert set(got["grad"]) == set(want)
    assert all(want[k] > 0 for k in want)
    for k, v in want.items():
        assert _rel(got["grad"][k], v) < 1e-6, k


@pytest.mark.parametrize("block", [0, 7, 8800])
def test_eval_matches_a_loop_over_pairs_at_any_block(pair_model, block):
    stream, src, dst, t = tiny_stream()
    batch = tiny_batches(src, dst, t, [300], 16, 5, valid=13)[0]
    params = _params()
    pos, neg = check.eval_scores(SPEC, stream, batch, params, block=block)
    with jax.default_matmul_precision("highest"):
        want_pos, want_neg = (np.asarray(x, np.float64)
                              for x in _loop(params, src, dst, batch))
    scale = max(np.abs(want_pos).max(), np.abs(want_neg).max())
    np.testing.assert_allclose(pos, want_pos, rtol=1e-6, atol=1e-6 * scale)
    np.testing.assert_allclose(neg, want_neg, rtol=1e-6, atol=1e-6 * scale)
    # Some pairs share neighbors, so the pair's own term is exercised.
    assert any(set(_neighbors(src, dst, int(u), 300))
               & set(_neighbors(src, dst, int(w), 300))
               for u, row in zip(batch["src"], batch["neg"]) for w in row)


def test_eval_of_a_pair_model_with_state_is_refused(pair_model,
                                                    monkeypatch):
    stream, src, dst, t = tiny_stream()
    batch = tiny_batches(src, dst, t, [300], 16, 5)[0]
    monkeypatch.setattr(REF, "init_state",
                        lambda kw, num_nodes, dtype: jnp.zeros(num_nodes))
    with pytest.raises(NotImplementedError, match="replay"):
        check.eval_scores(SPEC, stream, batch, _params())


@pytest.mark.parametrize("train", [False, True])
def test_a_model_without_the_kernel_counts_no_calls(pair_model, train):
    arrays = cell_batch("tgn")
    kw = {"d_model": 100}  # no d_time or num_heads: nothing sizes a call
    calls, ops = steps.step_work(NAME, kw, 10, 172, 9000, arrays, train)
    assert calls == []
    valid = np.concatenate([arrays["batch_mask"]] * 3)
    slots = (arrays["nbr_mask"] & valid[:, None]).sum()
    fwd = 163 * 3 * 100 + slots * 200 + 163 * 2 * 401
    assert ops == (3 * fwd + steps.ADAMW_OPS * 1000 if train else fwd)


def test_a_norm_gain_is_drawn_near_one():
    tree = weights.make(2**31 + 7, {"ln_0": {"scale": (20000,),
                                             "bias": (20000,)},
                                    "scale_w": {"w": (100, 100)}})
    scale, bias = (np.asarray(tree["ln_0"][k]) for k in ("scale", "bias"))
    assert abs(scale.mean() - 1) < 1e-3 and 0.019 < scale.std() < 0.021
    assert abs(bias.mean()) < 1e-3 and 0.019 < bias.std() < 0.021
    # Only a leaf named `scale` is a gain.
    w = np.asarray(tree["scale_w"]["w"])
    assert abs(w.mean()) < 0.01 and 0.09 < w.std() < 0.11
