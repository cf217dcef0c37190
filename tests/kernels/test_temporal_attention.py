"""Temporal-attention family extras beyond the shared parity harness:
exact-zero guarantees for empty neighborhoods, end-to-end device-sampler
wiring, the full backward-kernel gradient surface (bias-fold weights
included), the hop-2-aware and per-seed-table variants, and the
duplicate-neighbor read-modify-write accumulation path."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.temporal_attention import (
    fused_recency_attention_kernel,
    fused_temporal_layer,
    fused_temporal_layer_hop2,
    fused_temporal_layer_kernel,
    fused_temporal_layer_per_seed,
    temporal_attention_kernel,
)
from repro.kernels.temporal_attention.ref import (
    fused_recency_attention_ref,
    temporal_attention_ref,
)
from tests.kernels.families import fused_layer_inputs

RNG = np.random.default_rng(42)


def test_temporal_attention_empty_neighborhood_is_zero():
    S, K, H, D = 8, 4, 2, 16
    q = jnp.asarray(RNG.standard_normal((S, H, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((S, K, H, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((S, K, H, D)), jnp.float32)
    mask = jnp.zeros((S, K), bool)
    out = temporal_attention_kernel(q, k, v, mask, block_s=8, interpret=True)
    np.testing.assert_allclose(out, 0.0)


def test_fused_recency_attention_consumes_device_sampler_state():
    """End-to-end: DeviceRecencySampler buffers feed the fused kernel and
    agree with sampling + explicit gather + the plain oracle."""
    from repro.core.device_sampler import DeviceRecencySampler

    rng = np.random.default_rng(0)
    N, K, H, D, B = 30, 5, 2, 16, 40
    s = DeviceRecencySampler(N, K)
    src = rng.integers(0, N, B)
    dst = rng.integers(0, N, B)
    t = np.sort(rng.integers(0, 100, B))
    s.update(src, dst, t)

    seeds = jnp.asarray(rng.integers(0, N, 16), jnp.int32)
    q = jnp.asarray(rng.standard_normal((16, H, D)), jnp.float32)
    tbl = jnp.asarray(rng.standard_normal((N + 1, H, D)), jnp.float32)
    got = fused_recency_attention_kernel(q, tbl, tbl, seeds, s.buffer_ids,
                                         block_s=16, interpret=True)

    blk = s.sample(seeds)
    safe = jnp.maximum(blk.nbr_ids, 0)
    want = temporal_attention_ref(q, tbl[safe], tbl[safe], blk.mask)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_fused_temporal_layer_empty_rows_are_zero():
    (q, kt, vt, seeds, seed_t, _), kw = fused_layer_inputs(
        np.random.default_rng(1), 16, 4, 2, 16, 20, 8, 0)
    buf = jnp.asarray(np.stack([np.full((20, 4), -1), np.zeros((20, 4)),
                                np.full((20, 4), -1)], -1), jnp.int32)
    kw.pop("block_s")
    out = fused_temporal_layer_kernel(q, kt, vt, seeds, seed_t, buf,
                                      block_s=8, interpret=True, **kw)
    np.testing.assert_allclose(out, 0.0)


def test_fused_temporal_layer_negative_seeds_zero_rows_and_grads():
    """Hop-2 padding contract: seeds < 0 produce exactly-zero output rows,
    and contribute exactly zero to every gradient."""
    rng = np.random.default_rng(3)
    args, kw = fused_layer_inputs(rng, 12, 4, 2, 16, 20, 8, 0)
    q, kt, vt, seeds, seed_t, buf = args
    neg = jnp.asarray(np.where(np.arange(12) % 3 == 0, -1,
                               np.asarray(seeds)), jnp.int32)
    out = fused_temporal_layer(q, kt, vt, neg, seed_t, buf,
                               mode="interpret", **kw)
    np.testing.assert_allclose(out[::3], 0.0)

    def loss(q, s):
        o = fused_temporal_layer(q, kt, vt, s, seed_t, buf,
                                 mode="interpret", **kw)
        return jnp.sum(jnp.sin(o))

    gq = jax.grad(loss)(q, neg)
    np.testing.assert_allclose(gq[::3], 0.0)


def test_fused_temporal_layer_full_gradient_surface():
    """Backward kernel parity on *every* differentiable operand, including
    the in-kernel time/edge bias-fold weights — the gradients the oracle
    backward used to produce by materializing (S, K, ·) intermediates."""
    rng = np.random.default_rng(7)
    args, kw = fused_layer_inputs(rng, 24, 6, 2, 16, 30, 12, 5, w_scale=0.2)
    q, kt, vt, seeds, seed_t, buf = args
    names = ["q", "k_table", "v_table", "time_w", "time_b", "wt_k", "wt_v",
             "we_k", "we_v"]
    diff = {"q": q, "k_table": kt, "v_table": vt,
            **{n: kw[n] for n in names[3:]}}

    def loss(diff, mode):
        out = fused_temporal_layer(
            diff["q"], diff["k_table"], diff["v_table"], seeds, seed_t, buf,
            time_w=diff["time_w"], time_b=diff["time_b"],
            wt_k=diff["wt_k"], wt_v=diff["wt_v"],
            edge_feats=kw["edge_feats"], we_k=diff["we_k"],
            we_v=diff["we_v"], block_s=8, mode=mode)
        return jnp.sum(jnp.sin(out))

    g_kernel = jax.grad(loss)(diff, "interpret")
    g_ref = jax.grad(loss)(diff, "ref")
    for n in names:
        np.testing.assert_allclose(g_kernel[n], g_ref[n], rtol=1e-4,
                                   atol=1e-4, err_msg=n)


def test_fused_temporal_layer_duplicate_neighbor_rmw():
    """A buffer row listing the *same* neighbor in several slots exercises
    the backward's sequential DMA read-modify-write into dk/dv tables —
    the accumulation must not lose updates."""
    rng = np.random.default_rng(11)
    args, kw = fused_layer_inputs(rng, 8, 6, 2, 16, 10, 8, 0)
    kw.pop("block_s")
    q, kt, vt, seeds, seed_t, buf = args
    dup = np.array(buf)
    dup[:, :4, 0] = 3  # same neighbor id in four slots of every row
    dup = jnp.asarray(dup)

    def loss(kt, vt, mode):
        o = fused_temporal_layer(q, kt, vt, seeds, seed_t, dup,
                                 block_s=8, mode=mode, **kw)
        return jnp.sum(jnp.sin(o))

    gk = jax.grad(loss, (0, 1))(kt, vt, "interpret")
    gr = jax.grad(loss, (0, 1))(kt, vt, "ref")
    for name, a, b in zip(("dk_table", "dv_table"), gk, gr):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4, err_msg=name)


def test_fused_temporal_layer_hop2_variant():
    """Hop-2 wrapper: an (S, K) frontier (with -1 padding) flattens onto
    the hop-2-aware kernel; forward and gradients match the ref path."""
    rng = np.random.default_rng(5)
    S, K, H, D, N = 6, 4, 2, 16, 20
    args, kw = fused_layer_inputs(rng, S * K, K, H, D, N, 8, 0)
    kw.pop("block_s")
    q, kt, vt, _, _, buf = args
    frontier = jnp.asarray(rng.integers(-1, N, (S, K)), jnp.int32)
    f_times = jnp.asarray(rng.integers(0, 50, (S, K)), jnp.int32)

    def loss(q, kt, mode):
        o = fused_temporal_layer_hop2(q, kt, vt, frontier, f_times, buf,
                                      block_s=8, mode=mode, **kw)
        return jnp.sum(jnp.sin(o))

    out_k = fused_temporal_layer_hop2(q, kt, vt, frontier, f_times, buf,
                                      block_s=8, mode="interpret", **kw)
    out_r = fused_temporal_layer_hop2(q, kt, vt, frontier, f_times, buf,
                                      mode="ref", **kw)
    np.testing.assert_allclose(out_k, out_r, rtol=2e-5, atol=2e-5)
    pad = np.asarray(frontier.reshape(-1)) < 0
    np.testing.assert_allclose(np.asarray(out_k)[pad], 0.0)
    gk = jax.grad(loss, (0, 1))(q, kt, "interpret")
    gr = jax.grad(loss, (0, 1))(q, kt, "ref")
    for name, a, b in zip(("dq", "dk_table"), gk, gr):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4, err_msg=name)


def test_fused_temporal_layer_per_seed_variant():
    """Per-seed-table wrapper: seeds attend over their own K rows; masked
    slots drop out; an all-masked seed yields a zero row; gradients flow
    into the per-seed rows and match the ref path."""
    rng = np.random.default_rng(9)
    S, K, H, D = 6, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((S, H, D)) * 0.25, jnp.float32)
    k_rows = jnp.asarray(rng.standard_normal((S * K, H, D)) * 0.25,
                         jnp.float32)
    v_rows = jnp.asarray(rng.standard_normal((S * K, H, D)) * 0.25,
                         jnp.float32)
    seed_t = jnp.asarray(rng.integers(50, 120, S), jnp.int32)
    nbr_t = jnp.asarray(rng.integers(0, 50, (S, K)), jnp.int32)
    mask = np.asarray(rng.integers(0, 2, (S, K)), bool)
    mask[2] = False  # an all-masked seed
    mask = jnp.asarray(mask)
    kw = dict(
        time_w=jnp.asarray(rng.standard_normal(8) * 0.1, jnp.float32),
        time_b=jnp.asarray(rng.standard_normal(8) * 0.1, jnp.float32),
        wt_k=jnp.asarray(rng.standard_normal((8, H * D)) * 0.25, jnp.float32),
        wt_v=jnp.asarray(rng.standard_normal((8, H * D)) * 0.25, jnp.float32),
    )

    def run(q, kr, vr, mode):
        return fused_temporal_layer_per_seed(
            q, kr, vr, seed_t, nbr_t, mask, block_s=8, mode=mode, **kw)

    out_k = run(q, k_rows, v_rows, "interpret")
    out_r = run(q, k_rows, v_rows, "ref")
    np.testing.assert_allclose(out_k, out_r, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(out_k[2], 0.0)

    def loss(q, kr, vr, mode):
        return jnp.sum(jnp.sin(run(q, kr, vr, mode)))

    gk = jax.grad(loss, (0, 1, 2))(q, k_rows, v_rows, "interpret")
    gr = jax.grad(loss, (0, 1, 2))(q, k_rows, v_rows, "ref")
    for name, a, b in zip(("dq", "dk_rows", "dv_rows"), gk, gr):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4, err_msg=name)
    # masked rows get zero gradient (they never enter the softmax)
    flat_mask = np.asarray(mask).reshape(-1)
    np.testing.assert_allclose(np.asarray(gk[1])[~flat_mask], 0.0)


# --- blocked kernels: a block of B seeds per step, forward and backward ----

_GRAD_NAMES = ("q", "k_table", "v_table", "time_w", "time_b", "wt_k",
               "wt_v", "we_k", "we_v")


def _blocked_case(S, K, N, block_s, *, folds=True, dup=None, neg_seeds=0):
    """Fused-layer inputs for a blocking case: ``dup="within"`` draws every
    neighbor id from three nodes (repeats inside each block), ``"across"``
    gives every seed the same node (the same ids in every block)."""
    rng = np.random.default_rng(S * 131 + K * 7 + (block_s or 0))
    args, kw = fused_layer_inputs(rng, S, K, 2, 16, N, 8 if folds else 0,
                                  5 if folds else 0, neg_seeds=neg_seeds,
                                  w_scale=0.2)
    q, kt, vt, seeds, seed_t, buf = args
    buf = np.array(buf)
    if dup == "within":
        buf[..., 0] = np.where(buf[..., 0] >= 0, buf[..., 0] % 3, -1)
    if dup == "across":
        seeds = jnp.where(seeds >= 0, 5, -1).astype(jnp.int32)
        buf[5, :, 0] = np.arange(K) % 4
    kw["block_s"] = block_s
    return (q, kt, vt, seeds, seed_t, jnp.asarray(buf)), kw


@pytest.mark.parametrize("S,K,N,block_s,opts", [
    pytest.param(37, 20, 50, 8, {}, id="s_not_multiple_of_b-k20"),
    pytest.param(12, 10, 30, 1, {}, id="b1-k10"),
    pytest.param(12, 10, 30, 64, {}, id="b_over_s-k10"),
    pytest.param(24, 10, 30, 8, {"dup": "within"}, id="dup_ids_within_block"),
    pytest.param(24, 20, 30, 8, {"dup": "across"}, id="dup_ids_across_blocks"),
    pytest.param(40, 20, 30, 8, {"neg_seeds": 11}, id="hop2_negative_seeds"),
    pytest.param(20, 10, 30, 8, {"folds": False}, id="no_folds-k10"),
    pytest.param(40, 20, 30, None, {}, id="derived_block-k20"),
])
def test_blocked_layer_forward_and_gradients(S, K, N, block_s, opts):
    """The blocked forward and backward kernels match the oracle on every
    differentiable operand, whatever the block: S not a multiple of B, B=1,
    B>S, repeated neighbor ids within and across blocks (the backward's
    read-modify-write), hop-2 padding seeds, K=10 and K=20, bias folds on
    and off, and the block the wrapper derives itself."""
    (q, kt, vt, seeds, seed_t, buf), kw = _blocked_case(S, K, N, block_s,
                                                        **opts)
    bs = kw.pop("block_s")
    edge = kw.pop("edge_feats", None)
    diff = {"q": q, "k_table": kt, "v_table": vt, **kw}

    def run(diff, mode):
        return fused_temporal_layer(
            diff["q"], diff["k_table"], diff["v_table"], seeds, seed_t, buf,
            edge_feats=edge, **{n: diff[n] for n in _GRAD_NAMES[3:]
                                if n in diff},
            block_s=bs, mode=mode)

    np.testing.assert_allclose(run(diff, "interpret"), run(diff, "ref"),
                               rtol=2e-5, atol=2e-5)
    loss = lambda d, mode: jnp.sum(jnp.sin(run(d, mode)))  # noqa: E731
    g_kernel = jax.grad(loss)(diff, "interpret")
    g_ref = jax.grad(loss)(diff, "ref")
    for n in diff:
        np.testing.assert_allclose(g_kernel[n], g_ref[n], rtol=1e-4,
                                   atol=1e-4, err_msg=n)


@pytest.mark.parametrize("S,K,block_s", [(37, 20, 8), (12, 10, 1),
                                         (12, 10, 64), (30, 10, None)])
def test_blocked_recency_attention_without_folds(S, K, block_s):
    """The ids-only wrapper (no time or edge fold) runs the same blocked
    body: parity with the gather + oracle for any block."""
    rng = np.random.default_rng(S + K)
    N, H, D = 40, 2, 16
    q = jnp.asarray(rng.standard_normal((S, H, D)), jnp.float32)
    kt = jnp.asarray(rng.standard_normal((N, H, D)), jnp.float32)
    vt = jnp.asarray(rng.standard_normal((N, H, D)), jnp.float32)
    seeds = jnp.asarray(rng.integers(0, N, S), jnp.int32)
    ids = jnp.asarray(rng.integers(-1, 4, (N, K)), jnp.int32)
    got = fused_recency_attention_kernel(q, kt, vt, seeds, ids,
                                         block_s=block_s, interpret=True)
    want = fused_recency_attention_ref(q, kt, vt, seeds, ids)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("S,K,width,block,tile,pad", [
    (12_000, 20, 512, 16, 128, 32),    # TGAT hop-2 frontier, both folds
    (600, 20, 512, 16, 128, 40),       # TGAT's 600-seed layers
    (88_000, 20, 512, 16, 128, 64),    # the eval frontier
    (600, 10, 512, 32, 128, 40),       # TGN, K=10
    (600, 20, 256, 32, 128, 40),       # ids-only, no folds
    (5, 20, 512, 8, 8, 3),             # a serving microbatch: B <= S up to 8
])
def test_block_plan_follows_shapes(S, K, width, block, tile, pad):
    """B comes from the staging budget (2 slots x K padded rows of
    ``width`` lanes), capped at S rounded up to 8; a grid step walks up
    to 128 seeds in whole blocks, padding less than one block a step."""
    from repro.kernels.temporal_attention.kernel import _STAGE_BYTES, _plan

    m = _plan(S, K, width, None, "test")
    assert (m["block_s"], m["nblk"] * m["block_s"], m["pad"]) == (
        block, tile, pad)
    assert (S + m["pad"]) == m["ns"] * m["nblk"] * m["block_s"]
    assert 2 * block * K * 8 * width * 4 <= _STAGE_BYTES


def test_block_plan_is_recorded_once_per_traced_call_shape():
    """As it traces, the wrapper records S, the chosen B and the padded
    share of seed slots in every enabled telemetry, per call shape."""
    from repro.obs import MemorySink, Telemetry

    sink = MemorySink()
    tel = Telemetry(sink)
    (q, kt, vt, seeds, seed_t, buf), kw = _blocked_case(37, 20, 50, None)
    kw.pop("block_s")
    f = jax.jit(lambda q: fused_temporal_layer(q, kt, vt, seeds, seed_t, buf,
                                               mode="interpret", **kw))
    f(q)
    f(q)                                   # cached: no second record
    tel.detach(sink)
    recs = [r for r in sink.records if r["name"] == "kernel/attention_forward"]
    assert len(recs) == 1
    assert recs[0]["attrs"] == {"seeds": 37, "slots": 20, "block": 16,
                                "tile": 48, "pad_share": 11 / 48}


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_lowered_kernel_size_does_not_grow_with_the_block(direction):
    """The kernel body is rolled: lowered for the TPU at blocks of 8 and 64
    seeds, the module text is the same size within 3%. (Unrolling the
    block's seeds in Python made it ~20x larger, and its lowering seconds
    were paid again in every process.)"""
    (q, kt, vt, seeds, seed_t, buf), kw = _blocked_case(128, 20, 50, None)
    kw.pop("block_s")

    def fn(q, kt, bs):
        out = fused_temporal_layer(q, kt, vt, seeds, seed_t, buf, block_s=bs,
                                   mode="kernel", **kw)
        return out if direction == "forward" else jnp.sum(jnp.sin(out))

    sizes = []
    for bs in (8, 64):
        f = functools.partial(fn, bs=bs)
        if direction == "backward":
            f = jax.grad(f, argnums=(0, 1))
        lowered = jax.jit(f).trace(q, kt).lower(lowering_platforms=("tpu",))
        text = lowered.as_text()
        assert "tpu_custom_call" in text
        sizes.append(len(text))
    assert abs(sizes[1] - sizes[0]) <= 0.03 * sizes[0], sizes
