"""A run whose timed path is broken underneath reads ``correct`` false:
the check is not one that any output passes."""

from __future__ import annotations

import jax.numpy as jnp
import pytest

from chip.rehearse import rehearse


def _state_unchanged(pipe):
    """Each train step computes its loss but returns the state it got."""
    step = pipe._train_step
    if pipe.model_name == "tgn":
        pipe._train_step = lambda p, o, s, b: (p, o, s, step(p, o, s, b)[3])
    else:
        pipe._train_step = lambda p, o, b: (p, o, step(p, o, b)[2])


def _half_batch(pipe):
    """Each train step leaves out half of its batch and averages its loss
    over the rest."""
    step = pipe._train_step

    def cut(b):
        mask = b["batch_mask"]
        return dict(b, batch_mask=mask & (jnp.arange(mask.size)
                                          < mask.size // 2))

    if pipe.model_name == "tgn":
        pipe._train_step = lambda p, o, s, b: step(p, o, s, cut(b))
    else:
        pipe._train_step = lambda p, o, b: step(p, o, cut(b))


def _answer_altered(pipe):
    """Each eval batch's first positive score is altered where it is
    produced."""
    step = pipe._eval_step

    def altered(p, b):
        pos, neg = step(p, b)
        return pos.at[0].add(0.5), neg

    pipe._eval_step = altered


@pytest.mark.parametrize("workload,fault", [
    ("tgn-wiki.train", _state_unchanged),
    ("tgn-wiki.train", _half_batch),
    ("tgat-wiki.train", _state_unchanged),
    ("tgat-wiki.train", _half_batch),
    ("tgat-wiki.eval", _answer_altered),
], ids=["state_unchanged", "half_batch", "tgat_state_unchanged",
        "tgat_half_batch", "answer_altered"])
def test_a_broken_timed_path_is_not_correct(capsys, workload, fault):
    rc, line = rehearse(capsys, workload, fault=fault)
    assert rc == 0
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["check"].values())
