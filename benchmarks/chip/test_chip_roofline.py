"""Work counts of the fused attention calls and of whole steps, against a
hand count at one small shape."""

from __future__ import annotations

import numpy as np
import pytest

from chip.roofline import attention as att
from chip.roofline import steps

DIMS = att.Dims(d=4, heads=2, d_time=3, d_edge=2, k=3)
PEAK = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def _small_call():
    # Query 0 (node 5) has slots (7, e10), (8, e11), (7, e12); query 1
    # (node 6) has (7, e10) and one empty slot; query 2 is padding.
    q = np.array([5, 6, -1])
    ids = np.array([[7, 8, 7], [7, -1, -1], [9, 9, 9]])
    eids = np.array([[10, 11, 12], [10, -1, -1], [13, 13, 13]])
    mask = ids >= 0
    return q, ids, eids, mask


def test_touched_counts_valid_slots_and_distinct_rows():
    c = att.touched(*_small_call())
    assert c == att.Call(queries=2, slots=4, node_rows=2, edge_rows=3,
                         buffer_rows=2)


def test_forward_and_backward_match_a_hand_count():
    c = att.touched(*_small_call())
    # Per valid slot: time code 3*3, bias products 4*4*(3+2), node adds,
    # scores and weighted sum 8*4, softmax 3*2; per query the q scale 4.
    per_slot = 9 + 80 + 32 + 6
    assert att.forward(DIMS, c).ops == 4 * per_slot + 2 * 4
    weights = 4 * (2 * 3 * 4 + 2 * 2 * 4 + 2 * 3)
    fwd_bytes = 4 * (2 * 2 * 4 + 2 * 2 + 3 * 3 * 2 + 2 * 4 * 2 + 2 * 3)
    assert att.forward(DIMS, c).bytes == fwd_bytes + weights
    bwd = att.backward(DIMS, c)
    assert bwd.ops == 2 * (4 * per_slot + 2 * 4)
    assert bwd.bytes == fwd_bytes + weights + 4 * (2 * 4 + 2 * 4 * 2) + weights


def test_least_time_is_the_larger_bound():
    assert att.Work(200.0, 5.0).seconds(PEAK) == 2.0
    assert att.Work(10.0, 50.0).seconds(PEAK) == 5.0


def test_final_hop_rows_are_its_slots():
    q, ids, eids, mask = _small_call()
    c = att.touched(q, ids, eids, mask, rows_are_slots=True)
    assert c.node_rows == c.slots == 4
    assert c.buffer_rows == q.size


@pytest.mark.parametrize("seed", range(8))
def test_distinct_rows_never_exceed_gathered_rows(seed):
    rng = np.random.default_rng(seed)
    q = rng.integers(-1, 30, size=50)
    ids = rng.integers(-1, 40, size=(50, 6))
    eids = np.where(ids >= 0, rng.integers(0, 100, size=(50, 6)), -1)
    c = att.touched(q, ids, eids, ids >= 0)
    assert c.node_rows <= c.slots and c.edge_rows <= c.slots
    assert c.buffer_rows <= c.queries
    gathered = att.Work(0.0, 4.0 * (2 * DIMS.d * c.slots
                                    + DIMS.d_edge * c.slots))
    distinct = 4.0 * (2 * DIMS.d * c.node_rows + DIMS.d_edge * c.edge_rows)
    assert distinct <= gathered.bytes


def _tgat_batch(b=2, n_neg=1, k=2):
    s = b * (2 + n_neg)
    rng = np.random.default_rng(0)
    nbr = rng.integers(0, 9, size=(s, k))
    return {
        "seed_nodes": np.arange(s), "batch_mask": np.array([True, False]),
        "nbr_ids": nbr, "nbr_eids": nbr + 100, "nbr_mask": np.ones((s, k), bool),
        "nbr2_ids": rng.integers(0, 9, size=(s * k, k)),
        "nbr2_eids": rng.integers(0, 50, size=(s * k, k)),
        "nbr2_mask": np.ones((s * k, k), bool),
    }


def test_padded_events_are_not_work():
    kw = {"d_model": 4, "d_time": 3, "num_heads": 2, "num_layers": 2}
    arrays = _tgat_batch()
    calls, ops = steps.step_work("tgat", kw, 2, 2, 10, arrays, train=False)
    # One valid event: src, dst and one negative are the valid queries.
    assert calls[0].ops > 0 and len(calls) == 3
    assert calls[0].bytes > 0
    full = dict(arrays, batch_mask=np.array([True, True]))
    _, ops_full = steps.step_work("tgat", kw, 2, 2, 10, full, train=False)
    assert ops < ops_full


def test_training_counts_forward_backward_and_adamw():
    kw = {"d_model": 4, "d_time": 3, "num_heads": 2, "num_layers": 2}
    arrays = _tgat_batch()
    fwd_calls, fwd = steps.step_work("tgat", kw, 2, 2, 10, arrays, False)
    calls, ops = steps.step_work("tgat", kw, 2, 2, 10, arrays, True)
    assert len(calls) == 2 * len(fwd_calls)
    params = steps.model("tgat").num_params(kw, 10, 2)
    assert ops == 3 * fwd + steps.ADAMW_OPS * params


def test_param_count_matches_the_weight_layout():
    from chip import weights

    for name, kw in (("tgat", {"d_model": 4, "d_time": 3, "num_heads": 2,
                               "num_layers": 2}),
                     ("tgn", {"d_model": 4, "d_time": 3, "num_heads": 2,
                              "d_memory": 5})):
        shapes = weights.layout({"name": name, "kwargs": kw}, 10, 2)
        n = sum(int(np.prod(s)) for s in _leaves(shapes))
        assert steps.model(name).num_params(kw, 10, 2) == n


def _leaves(tree):
    for v in tree.values():
        if isinstance(v, dict):
            yield from _leaves(v)
        else:
            yield v
