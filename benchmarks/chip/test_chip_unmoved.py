"""The reference, the seed weights and the work counts of the TGAT and TGN
cells, pinned: the readings each gives at a tiny size, the hash of each
configuration's seed weights, and the work of a step at the cells' widths
must not move when the benchmark learns new kinds of model."""

from __future__ import annotations

import hashlib
import json

import jax
import numpy as np
import pytest

from chip import run, weights
from chip.reference import check
from chip.roofline import steps

TINY = {"tgat": {"d_model": 8, "d_time": 4, "num_heads": 2, "num_layers": 2},
        "tgn": {"d_model": 8, "d_time": 4, "num_heads": 2, "d_memory": 6}}
OPT = {"lr": 1e-2, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
NODES, EVENTS, D_EDGE, K = 24, 360, 4, 3


def tiny_stream(seed: int = 0):
    """Sources among the first half of the nodes, targets among the
    second, strictly rising times: ``(Stream, src, dst, t)``."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, NODES // 2, EVENTS)
    dst = rng.integers(NODES // 2, NODES, EVENTS)
    t = np.cumsum(rng.integers(1, 50, EVENTS))
    feats = rng.normal(size=(EVENTS, D_EDGE)).astype(np.float32)
    return check.Stream(src, dst, t, feats, K), src, dst, t


def tiny_batches(src, dst, t, los, b: int, n_neg: int, valid=None,
                 seed: int = 1):
    """Batches of ``b`` events from each ``lo``; the last one holds
    ``valid`` events and padding."""
    rng = np.random.default_rng(seed)
    out = []
    for i, lo in enumerate(los):
        m = valid if (valid is not None and i == len(los) - 1) else b
        pad = np.zeros(b, np.int64)
        batch = {"lo": lo, "mask": np.arange(b) < m,
                 "neg": rng.integers(0, NODES, (b, n_neg))}
        for key, col in (("src", src), ("dst", dst), ("time", t)):
            batch[key] = pad.copy()
            batch[key][:m] = col[lo:lo + m]
        out.append(batch)
    return out


def train_readings(name: str) -> dict:
    stream, src, dst, t = tiny_stream()
    spec = {"name": name, "kwargs": TINY[name]}
    params = weights.make(7, weights.layout(spec, NODES, D_EDGE))
    batches = tiny_batches(src, dst, t, [240, 260, 280], 16, 1, valid=11)
    return check.train(spec, OPT, stream, batches, params,
                       num_nodes=NODES)


def _digest(obj) -> str:
    return hashlib.sha256(json.dumps(obj, sort_keys=True).encode()).hexdigest()


# Readings of the reference as it was before it took models that score
# pairs (CPU, float32 at `highest`).
TRAIN = {
    "tgat": ([0.7005862593650818, 0.6856021881103516, 0.701088547706604],
             "f21f2486f8ccc0e3444a9985e03fc41b"
             "306bb896440d8198dcd6e2be4bb1dbe9"),
    "tgn": ([0.6997919082641602, 0.6933517456054688, 0.7026685476303101],
            "bd092ba1b332a390a993e209568cf19c"
            "100f6b6b4eff6dd935e143441e3056e1"),
}


@pytest.mark.parametrize("name", sorted(TRAIN))
def test_train_readings_are_the_pinned_ones(name):
    losses, digest = TRAIN[name]
    got = train_readings(name)
    assert got["losses"] == losses
    assert _digest(got) == digest


EVAL = "869cac79864833867ee7fc7ebb751002b7f6757d7c87edf7be5c5b6e748b6341"


def test_eval_scores_are_the_pinned_ones():
    stream, src, dst, t = tiny_stream()
    spec = {"name": "tgat", "kwargs": TINY["tgat"]}
    params = weights.make(7, weights.layout(spec, NODES, D_EDGE))
    batch = tiny_batches(src, dst, t, [300], 16, 5, valid=13)[0]
    pos, neg = check.eval_scores(spec, stream, batch, params, block=20)
    assert _digest([pos.tolist(), neg.tolist()]) == EVAL


def weight_digest(config: str, seed: int) -> str:
    cfg = json.loads((run.HERE / "configs" / f"{config}.json").read_text())
    shapes = weights.layout(cfg["model"], cfg["stream"]["nodes"],
                            cfg["stream"]["edge_feat_dim"])
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(
            weights.make(seed, shapes))[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(x).tobytes())
    return h.hexdigest()


WEIGHTS = {
    ("tgat-wiki", 3141592653):
        "ee67f99b22ae38d14e18e1e9bd57ab9ba498a5b7e9c727ae3df3eea90dbcc072",
    ("tgat-wiki", 2**33 + 5):
        "44513f44a43641d60c9dabf1fc6ba57d32444288135b6e7c8ef1150613fa05e9",
    ("tgn-wiki", 3141592653):
        "e51b057e89f6ab18d493026fb9da9f2b78252a6b654427fc3f565fc72c5d3572",
    ("tgn-wiki", 2**33 + 5):
        "d16f46362a0cbd6db0243c66433228e5266f56959779d18472859b76c1bdcd10",
}


@pytest.mark.parametrize("config,seed", sorted(WEIGHTS))
def test_seed_weights_are_the_pinned_ones(config, seed):
    assert weight_digest(config, seed) == WEIGHTS[config, seed]


def cell_batch(name: str, seed: int = 5) -> dict:
    """A step's batch arrays at the cell's shapes: 200 events (37 of them
    padding), one negative each, the cell's K, neighbors drawn over the
    stream's 9,000 nodes and 157,474 events."""
    rng = np.random.default_rng(seed)
    k = {"tgat": 20, "tgn": 10}[name]
    b, s, n, e = 200, 600, 9000, 157474
    mask = np.arange(b) < 163

    def block(rows):
        ids = rng.integers(0, n, (rows, k))
        valid = rng.random((rows, k)) < 0.7
        return (np.where(valid, ids, -1), np.where(valid, rng.integers(
            0, e, (rows, k)), -1), valid)

    arrays = {"seed_nodes": rng.integers(0, n, s), "batch_mask": mask,
              "src": rng.integers(0, 6000, b), "dst": rng.integers(6000, n, b)}
    arrays["nbr_ids"], arrays["nbr_eids"], arrays["nbr_mask"] = block(s)
    if name == "tgat":
        (arrays["nbr2_ids"], arrays["nbr2_eids"],
         arrays["nbr2_mask"]) = block(s * k)
    return arrays


def step_work_readings(config: str, train: bool):
    cfg = json.loads((run.HERE / "configs" / f"{config}.json").read_text())
    model = cfg["model"]
    calls, ops = steps.step_work(
        model["name"], model["kwargs"], cfg["sampler"]["k"],
        cfg["stream"]["edge_feat_dim"], cfg["stream"]["nodes"],
        cell_batch(model["name"]), train)
    return [[w.ops, w.bytes] for w in calls], ops


STEP_WORK = {
    ("tgat-wiki", False): (
        [[745431392.0, 9096552.0], [10467576110.0, 62990272.0],
         [745431392.0, 10741112.0]],
        17238445080.0),
    ("tgat-wiki", True): (
        [[745431392.0, 9096552.0], [10467576110.0, 62990272.0],
         [745431392.0, 10741112.0], [1490862784.0, 13320152.0],
         [20935152220.0, 73121472.0], [1490862784.0, 16580712.0]],
        51729620052.0),
    ("tgn-wiki", False): ([[377685916.0, 5307456.0]], 1116084138.0),
    ("tgn-wiki", True): (
        [[377685916.0, 5307456.0], [755371832.0, 8019856.0]],
        2847810042.0),
}


@pytest.mark.parametrize("config,train", sorted(STEP_WORK))
def test_step_work_is_the_pinned_one(config, train):
    calls, ops = STEP_WORK[config, train]
    assert step_work_readings(config, train) == (calls, ops)
