"""Distributed-correctness tests on an 8-device host-platform mesh.

Each test runs in a subprocess with XLA_FLAGS=--xla_force_host_platform_
device_count=8 so the main pytest process keeps its single-device view.
"""

import os
import subprocess
import sys
import textwrap


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, timeout=600, n_devices=8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_sharded_train_step_matches_single_device():
    """One train step on the debug mesh == the same step single-device."""
    run_sub("""
        import jax, numpy as np, jax.numpy as jnp
        from repro import configs
        from repro.models import registry
        from repro.distributed import sharding as SH
        from repro.launch.mesh import make_debug_mesh
        from repro.train import optim as OPT
        from repro.train.step import TrainConfig, make_train_step

        cfg = configs.get("gemma2-2b", reduced=True)
        model = registry.build(cfg)
        params = model.init(jax.random.key(0))
        opt = OPT.init(params)
        rng = np.random.default_rng(0)
        B, S = 4, 16
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S))),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S))),
            "positions": jnp.broadcast_to(jnp.arange(S), (B, S)),
        }
        tc = TrainConfig(compute_dtype=jnp.float32, remat=True,
                         use_chunked_ce=False)
        ocfg = OPT.AdamWConfig()

        # single device
        step1 = make_train_step(model, tc, ocfg, sc=None)
        p1, o1, m1 = jax.jit(step1)(params, opt, batch)

        # debug mesh
        mesh = make_debug_mesh()
        sc = SH.ShardingConfig(mesh, fsdp=True, seq_parallel=True)
        step2 = make_train_step(model, tc, ocfg, sc=sc)
        p_sh = SH.params_shardings(jax.eval_shape(lambda: params), sc)
        opt_sh = OPT.OptState(step=SH.replicated(sc), m=p_sh, v=p_sh)
        b_sh = SH.batch_specs(jax.eval_shape(lambda: batch), sc)
        params2 = jax.device_put(params, p_sh)
        opt2 = jax.device_put(opt, opt_sh)
        batch2 = jax.device_put(batch, b_sh)
        p2, o2, m2 = jax.jit(step2, in_shardings=(p_sh, opt_sh, b_sh),
                             out_shardings=(p_sh, opt_sh, None))(
                                 params2, opt2, batch2)

        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-4)
        for a, b in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-3)
        print("OK")
    """)


def test_moe_ep_matches_sorted_local():
    """shard_map EP MoE == local sorted dispatch (ample capacity)."""
    run_sub("""
        import jax, numpy as np, jax.numpy as jnp
        from repro import configs
        from repro.models import moe as MOE
        from repro.launch.mesh import make_debug_mesh

        cfg = configs.get("granite-moe-1b-a400m", reduced=True)  # 8e top4
        key = jax.random.key(0)
        p = MOE.moe_init(key, cfg)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(8, 16, cfg.d_model))
                        .astype(np.float32))

        ref, aux_ref = MOE.moe_apply_sorted(p, cfg, x,
                                            capacity_factor=32.0)
        mesh = make_debug_mesh()        # data=2, model=4 -> ep=4, epl=2
        got, aux = jax.jit(lambda p, x: MOE.moe_apply_ep(
            p, cfg, x, mesh=mesh, capacity_factor=32.0))(p, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)
        # aux is meaned per data shard in EP (GShard convention) vs global
        # in the local path: equal in expectation, not bitwise
        np.testing.assert_allclose(float(aux), float(aux_ref), rtol=5e-2)
        print("OK")
    """)


def test_moe_ep_replicated_experts():
    """ep > E path (mixtral-style): experts replicated across shards."""
    run_sub("""
        import jax, numpy as np, jax.numpy as jnp
        from repro import configs
        from repro.models import moe as MOE
        from repro.launch.mesh import make_debug_mesh

        cfg = configs.get("mixtral-8x7b", reduced=True)
        cfg = cfg.replace(n_experts=2, topk=2)   # ep=4 > E=2 -> r=2
        p = MOE.moe_init(jax.random.key(1), cfg)
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(4, 8, cfg.d_model))
                        .astype(np.float32))
        ref, _ = MOE.moe_apply_sorted(p, cfg, x, capacity_factor=32.0)
        mesh = make_debug_mesh()
        got, _ = jax.jit(lambda p, x: MOE.moe_apply_ep(
            p, cfg, x, mesh=mesh, capacity_factor=32.0))(p, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)
        print("OK")
    """)


def test_compressed_psum_error_feedback():
    """int8 cross-pod psum: bounded per-step error, error feedback keeps
    the RUNNING SUM exact to quantisation precision."""
    run_sub("""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.distributed import compression as C
        from repro.launch.mesh import make_debug_mesh

        mesh = make_debug_mesh(multi_pod=True)   # pod=2
        rng = np.random.default_rng(0)

        def one_round(x, err):
            def local(xl, e):
                m, e2 = C.compressed_psum(xl[0], "pod", e[0])
                return m[None], e2[None]
            return jax.shard_map(local, mesh=mesh, axis_names={"pod"},
                                 in_specs=(P("pod"), P("pod")),
                                 out_specs=(P("pod"), P("pod")))(x, err)

        shape = (2, 1, 300)                  # (pod, local_rows, dim)
        err = jnp.zeros(shape, jnp.float32)
        true_sum = np.zeros((1, 300), np.float32)
        got_sum = np.zeros((1, 300), np.float32)
        for t in range(20):
            x = rng.normal(size=shape).astype(np.float32)
            mean, err = one_round(jnp.asarray(x), err)
            mean = np.asarray(mean)
            # both pods must hold the identical exchanged mean
            np.testing.assert_array_equal(mean[0], mean[1])
            true_sum += x.mean(axis=0)
            got_sum += mean[0]
        # running sums track closely thanks to error feedback
        denom = np.abs(true_sum).mean()
        drift = np.abs(got_sum - true_sum).mean() / denom
        assert drift < 0.02, drift
        print("OK", drift)
    """)


def test_hierarchical_grads_compression():
    """Full wrapper: per-pod grads + compressed exchange ~= exact global
    grads; error buffers keep the optimizer trajectory on track."""
    run_sub("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.distributed import compression as C
        from repro.launch.mesh import make_debug_mesh

        mesh = make_debug_mesh(multi_pod=True)
        rng = np.random.default_rng(0)
        W = jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))
        batch = {"x": jnp.asarray(rng.normal(size=(16, 8))
                                  .astype(np.float32)),
                 "y": jnp.asarray(rng.normal(size=(16, 4))
                                  .astype(np.float32))}

        def grad_fn(w, b):
            def loss(w):
                return jnp.mean((b["x"] @ w - b["y"]) ** 2)
            l, g = jax.value_and_grad(loss)(w)
            return g, {"loss": l}

        exact, _ = grad_fn(W, batch)
        err = C.init_error_buffers(jax.eval_shape(lambda: W), n_pods=2)
        got, err2, metrics = jax.jit(
            lambda W, b, e: C.hierarchical_grads(grad_fn, mesh, W, b, e)
        )(W, batch, err)
        rel = float(jnp.max(jnp.abs(got - exact)) /
                    (jnp.max(jnp.abs(exact)) + 1e-9))
        assert rel < 0.02, rel        # int8 quantisation noise only
        print("OK", rel)
    """)


def test_pipeline_matches_sequential():
    """2-stage GPipe over 'pod' == plain scan over all bodies."""
    run_sub("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.distributed import pipeline as PP
        from repro.launch.mesh import make_debug_mesh

        mesh = make_debug_mesh(multi_pod=True)   # pod=2
        rng = np.random.default_rng(0)
        n_bodies, d = 4, 16
        W = jnp.asarray(rng.normal(size=(n_bodies, d, d))
                        .astype(np.float32) * 0.3)
        x = jnp.asarray(rng.normal(size=(8, 4, d)).astype(np.float32))

        def body_fn(w, h):
            return jnp.tanh(h @ w)

        def seq(x, W):
            def sb(h, w):
                return body_fn(w, h), None
            y, _ = jax.lax.scan(sb, x, W)
            return y

        ref = seq(x, W)
        got = jax.jit(lambda W, x: PP.pipelined_forward(
            body_fn, W, x, mesh, n_micro=4))(W, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

        # and it differentiates (GPipe backward through the schedule)
        g = jax.grad(lambda W: jnp.sum(PP.pipelined_forward(
            body_fn, W, x, mesh, n_micro=4) ** 2))(W)
        gref = jax.grad(lambda W: jnp.sum(seq(x, W) ** 2))(W)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gref),
                                   rtol=1e-3, atol=1e-3)
        print("OK")
    """)


def test_sharded_serve_scheduler_matches_per_scene_loop():
    """shard_map-sharded scene-axis serving on the 8-device host mesh:
    the continuous-batching scheduler rounds max_batch up to the device
    count, shards micro-batches with shard_over_scenes, and produces the
    same segmentation as a single-device per-scene loop."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import mapping as M
        from repro.data.synthetic import lidar_scene
        from repro.distributed import sharding as SH
        from repro.models import minkunet as MU
        from repro.serve.buckets import geometric_ladder
        from repro.serve.engine import PointCloudEngine
        from repro.serve.scheduler import ServeScheduler

        assert len(jax.devices()) == 8
        mesh = SH.make_scene_mesh()
        assert mesh is not None and mesh.shape["scene"] == 8

        params = MU.mini_minkunet_init(jax.random.key(0), c_in=4,
                                       n_classes=2)
        engine = PointCloudEngine(params, n_stages=2, flow="fod",
                                  ladder=geometric_ladder(64, 128))
        sched = ServeScheduler(engine, max_batch=6, mesh="auto")
        assert sched.mesh is not None
        assert sched.max_batch == 8      # rounded up to the device count

        sizes = [40, 90, 60, 120] * 4    # 16 scenes, 2 buckets
        scenes = [lidar_scene(seed=5 + i % 8, n_points=n, grid=20)
                  for i, n in enumerate(sizes)]
        rids = [sched.submit(c, f, m) for (c, m, f) in scenes]
        sched.flush()
        by_rid = {r.rid: r for r in sched.drain()}
        assert sorted(by_rid) == rids

        for rid, (c, m, f) in zip(rids, scenes):
            pc = M.make_point_cloud(jnp.asarray(c), jnp.asarray(m))
            logits = MU.minkunet_apply(params, pc, jnp.asarray(f),
                                       flow="fod")
            np.testing.assert_array_equal(
                by_rid[rid].preds, np.asarray(jnp.argmax(logits, -1)))

        stats = sched.stats()
        assert stats["n_devices"] == 8
        assert stats["n_completed"] == 16
        assert len(stats["buckets"]) == 2
        print("OK")
    """)


def test_chip_smoke_four_chip_path_matches_one_device():
    """chip_smoke.py --four-chips at tiny size on 4 virtual devices: the
    scheduler shards micro-batches over a 4-device scene mesh and every
    scene agrees with the one-device per-scene reference."""
    out = run_sub(f"""
        import sys, jax
        sys.path.insert(0, {REPO!r})
        import chip_smoke as CS
        from repro.distributed import sharding as SH

        devs = jax.devices()
        assert len(devs) == 4
        scenes = CS.make_scenes(6, sizes=(90, 40), seed=2)
        params = CS.init_params(0, stem=8, enc_planes=(8, 16),
                                dec_planes=(16, 8), blocks_per_stage=1)
        mesh = SH.make_scene_mesh(devices=devs)
        preds, _, stats = CS.serve(params, scenes, max_batch=4, mesh=mesh)
        assert stats["n_devices"] == 4 and stats["n_completed"] == 6
        refs = CS.reference_logits(params, scenes, devs[0])
        reports = CS.check_served(preds, refs, scenes)
        assert all(r["ok"] for r in reports)
        print("OK", min(r["agree"] for r in reports))
    """, n_devices=4)
    assert "OK" in out


def test_checkpoint_roundtrip_and_elastic(tmp_path):
    """Save on one mesh, restore on a different mesh; atomic commit."""
    run_sub(f"""
        import os, jax, numpy as np, jax.numpy as jnp
        from repro.checkpoint import store
        from repro.distributed import sharding as SH
        from repro.launch.mesh import make_debug_mesh

        root = {str(tmp_path)!r}
        tree = {{"a": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                "b": {{"c": jnp.ones((4, 16), jnp.bfloat16)}}}}
        store.save(root, 5, tree)
        store.save(root, 7, tree)
        assert store.latest_step(root) == 7
        # uncommitted dir is ignored
        os.makedirs(os.path.join(root, "step_00000009"), exist_ok=True)
        assert store.latest_step(root) == 7

        like = jax.eval_shape(lambda: tree)
        mesh = make_debug_mesh()
        sc = SH.ShardingConfig(mesh, fsdp=True)
        sh = jax.tree_util.tree_map(
            lambda l: jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec("data")), like)
        out = store.restore(root, 7, like, sh)
        np.testing.assert_array_equal(np.asarray(out["a"]),
                                      np.asarray(tree["a"]))
        assert out["a"].sharding.spec == jax.sharding.PartitionSpec("data")
        print("OK")
    """)
