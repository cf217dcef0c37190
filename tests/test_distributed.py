"""Multi-device tests (shard_map DP trainer, sharding rules, mini dry-run,
elastic restore, sampler checkpoint resharding). These need >1 device, so
each runs in a subprocess with ``--xla_force_host_platform_device_count``
set before jax initializes (``tests/_forced_topology.py``).
"""

from tests._forced_topology import run_forced as _run


def test_sharding_rules_divisibility():
    out = _run("""
    import jax
    from repro.distributed.sharding import logical_spec
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    # divisible -> sharded; non-divisible -> dropped; missing axis -> dropped
    s1 = logical_spec(("batch", "mlp"), mesh=mesh, shape=(8, 16))
    s2 = logical_spec(("batch", "mlp"), mesh=mesh, shape=(8, 5))
    s3 = logical_spec(("batch", None), mesh=mesh, shape=(3, 5))
    print(s1, "|", s2, "|", s3)
    """)
    assert "'data', 'model'" in out.replace('"', "'") or "data" in out
    parts = out.strip().split("|")
    assert "model" not in parts[1]
    assert "data" not in parts[2]


def test_dp_trainer_matches_single_device():
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.dp_trainer import DataParallelTrainer
    from repro.optim import AdamWConfig, adamw_init, adamw_update

    mesh = jax.make_mesh((4,), ("data",))
    D = 8
    def loss_fn(params, state, batch):
        h = batch["x"] @ params["w"]
        return ((h - 1.0) ** 2).mean(), (state, None)

    params = {"w": jnp.eye(D)}
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 16, D)), jnp.float32)

    tr = DataParallelTrainer(loss_fn, mesh, AdamWConfig(lr=1e-2))
    opt, err = tr.init(params)
    tr.build_step(stateful=False)
    err = {} if err is None else err
    p_dp, *_rest = tr._step(params, opt, err, {}, {"x": x})

    # single-device reference: same global batch, plain AdamW
    opt_ref = adamw_init(params)
    g = jax.grad(lambda p: ((x[0] @ p["w"] - 1.0) ** 2).mean())(params)
    p_ref, _ = adamw_update(params, g, opt_ref, AdamWConfig(lr=1e-2))
    np.testing.assert_allclose(np.asarray(p_dp["w"]), np.asarray(p_ref["w"]),
                               rtol=1e-5, atol=1e-5)
    print("MATCH")
    """, devices=4)
    assert "MATCH" in out


def test_int8_error_feedback_tracks_uncompressed():
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.dp_trainer import DataParallelTrainer
    from repro.optim import AdamWConfig
    mesh = jax.make_mesh((4,), ("data",))
    D = 8
    def loss_fn(params, state, batch):
        return ((batch["x"] @ params["w"] - 1.0) ** 2).mean(), (state, None)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 16, D)), jnp.float32)
    finals = {}
    for scheme in ("none", "int8_ef"):
        params = {"w": jnp.eye(D)}
        tr = DataParallelTrainer(loss_fn, mesh, AdamWConfig(lr=1e-2),
                                 compression=scheme)
        opt, err = tr.init(params)
        tr.build_step(stateful=False)
        err = {} if err is None else err
        loss = None
        for _ in range(30):
            params, opt, err, _st, loss = tr._step(params, opt, err, {}, {"x": x})
        finals[scheme] = float(loss)
    print("LOSSES", finals)
    assert finals["int8_ef"] < 1.2 * finals["none"] + 1e-3
    """, devices=4)
    assert "LOSSES" in out


def test_mini_dryrun_on_debug_mesh():
    """End-to-end dry-run machinery on an 8-device mesh with a reduced arch."""
    out = _run("""
    import dataclasses, jax
    from repro.configs import get_arch
    from repro.configs.base import ShapeConfig
    from repro.distributed.sharding import sharding_context, DEFAULT_RULES
    from repro.launch.specs import step_and_args
    from repro.launch import hlo_analysis

    # Auto axes: the model's sharding constraints need them.
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = dataclasses.replace(get_arch("qwen3-0.6b").reduced(),
                              scan_layers=True, remat=True,
                              param_dtype="bfloat16", compute_dtype="bfloat16")
    for shape in [ShapeConfig("t", 64, 8, "train"),
                  ShapeConfig("p", 64, 8, "prefill"),
                  ShapeConfig("d", 64, 8, "decode")]:
        with sharding_context(mesh, DEFAULT_RULES):
            step, args, _ = step_and_args(cfg, shape, mesh, kv_block=32)
            with mesh:
                compiled = jax.jit(step).lower(*args).compile()
        r = hlo_analysis.analyze(compiled, mesh.size)
        assert r.flops_per_device > 0
        print(shape.kind, "ok", r.dominant)
    """, devices=8)
    assert out.count("ok") == 3


def test_sampler_checkpoint_reshard_1_to_8_and_back(tmp_path):
    """Sampler/hook state saved on a 1-device mesh must restore onto an
    8-device mesh (and the reverse) through the real checkpoint machinery,
    with bit-identical subsequent sample draws (docs/sharding.md)."""
    out = _run(f"""
    import numpy as np
    from repro.core import DeviceRecencySampler, DeviceUniformSampler
    from repro.distributed import checkpoint as ckpt
    from repro.distributed.sharding import make_node_mesh

    rng = np.random.default_rng(0)
    N, k, E = 29, 4, 250
    src, dst = rng.integers(0, N, E), rng.integers(0, N, E)
    t = np.sort(rng.integers(0, 70, E))

    def warm_recency(s):
        for i in range(4):
            sl = slice(i * 40, (i + 1) * 40)
            s.update(src[sl], dst[sl], t[sl])

    for save_shards, load_shards in ((1, 8), (8, 1)):
        a = DeviceRecencySampler(N, k, mesh=make_node_mesh(save_shards))
        warm_recency(a)
        u = DeviceUniformSampler(N, k, seed=3,
                                 mesh=make_node_mesh(save_shards))
        u.build(src, dst, t)
        u.sample(rng.integers(0, N, 9), rng.integers(5, 80, 9))
        d = r"{tmp_path}" + f"/re_{{save_shards}}to{{load_shards}}"
        ckpt.save(d, 0, {{"recency": a.state_dict(),
                          "uniform": u.state_dict()}})

        b = DeviceRecencySampler(N, k, mesh=make_node_mesh(load_shards))
        v = DeviceUniformSampler(N, k, seed=3,
                                 mesh=make_node_mesh(load_shards))
        tree, _, _ = ckpt.restore(d, target=None)
        rec = {{kk.split("/", 1)[1]: vv for kk, vv in tree.items()
               if kk.startswith("recency/")}}
        uni = {{kk.split("/", 1)[1]: vv for kk, vv in tree.items()
               if kk.startswith("uniform/")}}
        b.load_state_dict(rec)
        v.load_state_dict(uni)

        seeds = rng.integers(0, N, 13)
        qa, qb = a.sample(seeds), b.sample(seeds)
        qt = rng.integers(10, 90, 13)
        # the restored uniform sampler continues the SAME draw counter
        ua, ub = u.sample(seeds, qt), v.sample(seeds, qt)
        for x, y in ((qa, qb), (ua, ub)):
            for f in ("nbr_ids", "nbr_times", "nbr_eids", "mask"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(x, f)), np.asarray(getattr(y, f)))
        print(f"RESHARD {{save_shards}}->{{load_shards}} OK")
    """)
    assert "RESHARD 1->8 OK" in out and "RESHARD 8->1 OK" in out


def test_sharded_pipeline_matches_single_device():
    """CTDGLinkPipeline with SamplerSpec.shards=4 must produce the exact
    same train losses as the unsharded device pipeline (the whole stack:
    recipe mesh plumbing, replicated batch staging, shard_map samplers,
    replicated jitted steps)."""
    out = _run("""
    import numpy as np
    from repro.data import generate
    from repro.tg.specs import SamplerSpec
    from repro.train.loop import CTDGLinkPipeline

    data = generate("tiny").slice_events(0, 300)

    def run(spec):
        p = CTDGLinkPipeline("tgat", data, batch_size=100, seed=0,
                             sampler_spec=spec)
        loss, _ = p.train_epoch()
        return loss

    l0 = run(SamplerSpec(device=True))
    l1 = run(SamplerSpec(device=True, shards=4))
    assert l0 == l1, (l0, l1)
    print("PIPELINE SHARDED OK", l0)
    """, devices=4)
    assert "PIPELINE SHARDED OK" in out


def test_sharded_fused_layer_bit_parity():
    """``fused_temporal_layer_sharded`` inside a shard_map over the node
    axis must be BIT-identical to the single-device layer: one owner per
    seed contributes its value, every other shard contributes exact zeros,
    and the psum of one value with zeros is exact. Gradients likewise."""
    out = _run("""
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import DeviceRecencySampler
    from repro.distributed.sharding import make_node_mesh
    from repro.kernels.temporal_attention import (
        fused_temporal_layer, fused_temporal_layer_sharded)

    rng = np.random.default_rng(0)
    N, K, H, D, S = 23, 4, 2, 8, 16
    plain = DeviceRecencySampler(N, K, retain_state=True)
    for _ in range(3):
        src, dst = rng.integers(0, N, 20), rng.integers(0, N, 20)
        t = np.sort(rng.integers(0, 50, 20))
        plain.update(src, dst, t)
    sd = plain.state_dict()

    q = jnp.asarray(rng.standard_normal((S, H, D)) * .25, jnp.float32)
    kt = jnp.asarray(rng.standard_normal((N, H, D)) * .25, jnp.float32)
    vt = jnp.asarray(rng.standard_normal((N, H, D)) * .25, jnp.float32)
    seeds = jnp.asarray(rng.integers(0, N, S), jnp.int32)
    seed_t = jnp.asarray(np.full(S, 60), jnp.int32)

    def ref_loss(q, kt):
        o = fused_temporal_layer(q, kt, vt, seeds, seed_t,
                                 plain.packed_buffer, mode="ref")
        return jnp.sum(jnp.sin(o)), o
    (_, out_ref), g_ref = jax.value_and_grad(
        ref_loss, (0, 1), has_aux=True)(q, kt)

    for shards in (2, 5, 8):
        mesh = make_node_mesh(shards, "nodes")
        sh = DeviceRecencySampler(N, K, mesh=mesh, mesh_axis="nodes",
                                  retain_state=True)
        sh.load_state_dict(sd)
        per = sh.rows_per_shard

        def body(q, kt, buf):
            def loss(q, kt):
                o = fused_temporal_layer_sharded(
                    q, kt, vt, seeds, seed_t, buf, axis="nodes",
                    rows_per_shard=per, mode="ref")
                return jnp.sum(jnp.sin(o)), o
            (_, o), g = jax.value_and_grad(loss, (0, 1),
                                           has_aux=True)(q, kt)
            return o, g

        smapped = jax.shard_map(body, mesh=mesh,
                                in_specs=(P(), P(), P("nodes")),
                                out_specs=(P(), (P(), P())),
                                check_vma=False)
        o, g = jax.jit(smapped)(q, kt, sh.packed_buffer)
        np.testing.assert_array_equal(np.asarray(o), np.asarray(out_ref))
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)
        print(f"SHARDED LAYER {shards} OK")
    """)
    for shards in (2, 5, 8):
        assert f"SHARDED LAYER {shards} OK" in out


def test_2d_pipeline_matches_single_device():
    """A jitted 2-D-mesh train epoch (data >= 2, nodes >= 2, fused path
    enabled) must match the single-device fused pipeline within the
    documented 1e-4 kernel grad bound — both 2x4 and 4x2 mesh shapes
    (docs/sharding.md)."""
    out = _run("""
    import numpy as np, jax
    from repro.data import generate
    from repro.tg.specs import SamplerSpec
    from repro.train.loop import CTDGLinkPipeline

    data = generate("tiny").slice_events(0, 300)

    def build(ds, ns):
        spec = SamplerSpec(kind="recency", device=True, shards=ns,
                           expose_buffer=True if ns else None)
        return CTDGLinkPipeline("tgat", data, batch_size=100, seed=0,
                                sampler_spec=spec, data_shards=ds,
                                fused="ref")

    ref = build(1, None)
    l0, _ = ref.train_epoch()
    leaves0 = jax.tree.leaves(ref.params)
    for ds, ns in ((2, 4), (4, 2)):
        p = build(ds, ns)
        assert p._mesh is not None and dict(p._mesh.shape) == {
            "data": ds, "nodes": ns}
        l1, _ = p.train_epoch()
        assert abs(l0 - l1) < 1e-4, (ds, ns, l0, l1)
        d = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                for a, b in zip(leaves0, jax.tree.leaves(p.params)))
        assert d < 1e-4, (ds, ns, d)
        print(f"2D {ds}x{ns} OK", l1, d)
    """)
    assert "2D 2x4 OK" in out and "2D 4x2 OK" in out


def test_2d_checkpoint_reshard_across_mesh_shapes(tmp_path):
    """A pipeline checkpoint written under one 2-D mesh shape must restore
    under any other (1x1 <-> 2x4 <-> 4x2) and continue training to the
    same losses — canonical sampler state + replicated params make
    checkpoints mesh-agnostic."""
    out = _run(f"""
    import numpy as np, jax
    from repro.data import generate
    from repro.tg.specs import SamplerSpec
    from repro.train.loop import CTDGLinkPipeline

    data = generate("tiny").slice_events(0, 300)

    def build(ds, ns):
        spec = SamplerSpec(kind="recency", device=True, shards=ns,
                           expose_buffer=True if ns else None)
        return CTDGLinkPipeline("tgat", data, batch_size=100, seed=0,
                                sampler_spec=spec, data_shards=ds,
                                fused="ref")

    # epoch 0 under 2x4, checkpoint, then epoch 1 under 1x1 / 2x4 / 4x2
    a = build(2, 4)
    a.train_epoch()
    d = r"{tmp_path}" + "/2d"
    a.save_checkpoint(d, 0)

    losses, params = [], []
    for ds, ns in ((1, None), (2, 4), (4, 2)):
        p = build(ds, ns)
        p.restore_checkpoint(d)
        l, _ = p.train_epoch()
        losses.append(l)
        params.append(jax.tree.leaves(p.params))
    for l, ps in zip(losses[1:], params[1:]):
        assert abs(l - losses[0]) < 1e-4, losses
        dmax = max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
                   for x, y in zip(params[0], ps))
        assert dmax < 1e-4, dmax
    print("2D RESHARD OK", losses)
    """)
    assert "2D RESHARD OK" in out


def test_elastic_restore_across_meshes(tmp_path):
    out = _run(f"""
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed import checkpoint as ckpt
    from repro.distributed.sharding import logical_sharding

    # save params sharded on a (4, 2) mesh
    mesh_a = jax.make_mesh((4, 2), ("data", "model"))
    w = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    w = jax.device_put(w, logical_sharding(("batch", "mlp"), mesh=mesh_a, shape=w.shape))
    ckpt.save(r"{tmp_path}", 0, {{"w": w}}, logical_axes={{"w": ("batch", "mlp")}})

    # restore onto a DIFFERENT mesh (2, 4): elastic re-shard
    mesh_b = jax.make_mesh((2, 4), ("data", "model"))
    tree, step, _ = ckpt.restore(r"{tmp_path}", target={{"w": w}}, mesh=mesh_b)
    got = tree["w"]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(w))
    assert got.sharding.mesh.shape["model"] == 4
    print("ELASTIC OK")
    """, devices=8)
    assert "ELASTIC OK" in out
