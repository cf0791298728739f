"""Multi-device SPMD tests (subprocess with 8 forced host devices, so the rest
of the suite keeps seeing 1 device as required by the brief)."""
import os
import subprocess
import sys
import textwrap


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str) -> str:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """2x4 mesh train step == single-device train step (same seeds)."""
    _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models.params import init_params
        from repro.optim.adamw import OptConfig
        from repro.runtime.train import (init_train_state, make_train_step,
                                         state_shardings, batch_shardings)
        cfg = get_config("qwen1.5-0.5b").smoke()
        params = init_params(cfg, jax.random.PRNGKey(0), max_seq=32)
        state = init_train_state(params)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
        opt = OptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
        # single device
        s1, m1 = jax.jit(make_train_step(cfg, opt))(state, batch)
        # sharded 2x4
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        step = make_train_step(cfg, opt, mesh=mesh, tp_total=4)
        st_sh = state_shardings(cfg, state, mesh)
        b_sh = batch_shardings(batch, mesh)
        with mesh:
            s2, m2 = jax.jit(step, in_shardings=(st_sh, b_sh),
                             out_shardings=(st_sh, None))(state, batch)
        l1, l2 = float(m1["loss"]), float(m2["loss"])
        assert abs(l1 - l2) / abs(l1) < 2e-2, (l1, l2)
        g1, g2 = float(m1["grad_norm"]), float(m2["grad_norm"])
        assert abs(g1 - g2) / abs(g1) < 2e-2, (g1, g2)
        for k in s1.params:
            if k.endswith(("/bq", "/bk", "/bv")):
                # zero-init biases: Adam's first update is +-lr * sign(g) and
                # tiny bf16 grads flip sign under different reduction orders
                continue
            a = np.asarray(s1.params[k], np.float32)
            b = np.asarray(jax.device_get(s2.params[k]), np.float32)
            rel = np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-6)
            assert rel < 5e-2, (k, rel)
        print("OK sharded==single")
    """))


def test_moe_shard_map_matches_local():
    """Expert-parallel shard_map output == local MoE block (same routing)."""
    _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_config
        from repro.models.moe import moe_block, MoELayerParams
        from repro.models.params import init_params, moe_factors
        cfg = dataclasses.replace(get_config("granite-moe-3b-a800m").smoke(),
                                  dtype="float32")
        # high capacity factor => no token drops => local/sharded bit-comparable
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=4.0))
        # local layout (tp_total=1)
        p1 = init_params(cfg, jax.random.PRNGKey(0), max_seq=32, tp_total=1)
        # sharded layout (tp_total=4): rebuild the same weights in EP layout
        E = cfg.moe.n_experts
        f = cfg.moe.d_ff_expert
        d = cfg.d_model
        ep, tp = moe_factors(E, 4)
        def to_ep(w, last_is_d):
            # (L, 1, E, d, f) -> (L, 4, E/ep, d, f/tp) matching moe layout
            L = w.shape[0]
            w = w[:, 0]
            if last_is_d:      # w_down (E, f, d): split f
                w = w.reshape(L, ep, E // ep, tp, f // tp, d)
                w = w.transpose(0, 1, 3, 2, 4, 5).reshape(L, 4, E // ep, f // tp, d)
            else:              # w_gate/up (E, d, f): split f
                w = w.reshape(L, ep, E // ep, d, tp, f // tp)
                w = w.transpose(0, 1, 4, 2, 3, 5).reshape(L, 4, E // ep, d, f // tp)
            return w
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, d), jnp.float32)
        lp = MoELayerParams(router=p1["layers/moe/router"][0],
                            w_gate=p1["layers/moe/w_gate"][0],
                            w_up=p1["layers/moe/w_up"][0],
                            w_down=p1["layers/moe/w_down"][0])
        y1, lb1, z1 = moe_block(x, lp, cfg, None, 1)
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        lp4 = MoELayerParams(router=p1["layers/moe/router"][0],
                             w_gate=to_ep(p1["layers/moe/w_gate"], False)[0],
                             w_up=to_ep(p1["layers/moe/w_up"], False)[0],
                             w_down=to_ep(p1["layers/moe/w_down"], True)[0])
        with mesh:
            y4, lb4, z4 = jax.jit(lambda x, p: moe_block(x, p, cfg, mesh, 4))(x, lp4)
        a, b = np.asarray(y1), np.asarray(jax.device_get(y4))
        rel = np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-6)
        assert rel < 1e-3, rel
        # aux losses aggregate per data shard (nonlinear in the routing
        # stats), so sharded != global exactly; sanity-range only
        assert 0.5 < float(lb4) / float(lb1) < 2.0, (float(lb1), float(lb4))
        print("OK moe ep==local", rel)
    """))


def test_production_mesh_constructs():
    _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        m2 = make_production_mesh(multi_pod=True)
        assert dict(m1.shape) == {"data": 16, "model": 16}
        assert dict(m2.shape) == {"pod": 2, "data": 16, "model": 16}
        print("OK meshes")
    """))


def test_dist_batched_executable_serves_indivisible_batches():
    """One DistWriter artifact on a 4-way data mesh serves batch 8 (sharded
    evenly), 3 and 1 (zero-padded to the DP multiple, output sliced back)."""
    _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.mnist_cnn import CONFIG as CNN
        from repro.models import cnn
        from repro.core.reader import cnn_to_ir
        from repro.core.passes import PassManager, structural_pipeline
        from repro.core.writers.dist_writer import DistWriter
        from jax.sharding import AxisType
        params = cnn.init_params(CNN, jax.random.PRNGKey(0))
        g = cnn_to_ir(CNN, {k: np.asarray(v) for k, v in params.items()})
        g = PassManager(structural_pipeline()).run(g)
        mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
        w = DistWriter(g)
        exe = w.build_batched(mesh)
        ref = w.build()
        x = jax.random.uniform(jax.random.PRNGKey(1), (8, 28, 28, 1))
        for b in (8, 3, 1):
            y = np.asarray(exe(x[:b]))
            assert y.shape == (b, 10), y.shape
            np.testing.assert_allclose(y, np.asarray(ref(x[:b])), atol=1e-5)
        assert exe.cached_batches == (8, 3, 1)
        # symbolic graphs refuse AOT lowering without a concrete batch
        try:
            w.lower_compile(mesh)
        except ValueError as e:
            assert "symbolic" in str(e)
        else:
            raise AssertionError("lower_compile should require batch=")
        print("OK dist batched")
    """))


def test_accel_server_coalesces_onto_mesh():
    """The batch-coalescing AccelServer drives DistWriter.build_batched on a
    4-way data mesh: mixed-size requests are packed, padded to LRU-aligned
    buckets, executed SPMD, and demuxed back per request."""
    _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.mnist_cnn import CONFIG as CNN
        from repro.models import cnn
        from repro.core.reader import cnn_to_ir
        from repro.core.passes import PassManager, structural_pipeline
        from repro.core.writers.dist_writer import DistWriter
        from jax.sharding import AxisType
        from repro.runtime.serve import AccelServer
        params = cnn.init_params(CNN, jax.random.PRNGKey(0))
        g = cnn_to_ir(CNN, {k: np.asarray(v) for k, v in params.items()})
        g = PassManager(structural_pipeline()).run(g)
        mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
        w = DistWriter(g)
        traced = []
        srv = AccelServer(w.build_batched(mesh, on_compile=traced.append),
                          max_batch=8, max_wait=0.0)
        ref = w.build()
        x = jax.random.uniform(jax.random.PRNGKey(1), (8, 28, 28, 1))
        sizes = (2, 3, 1, 4, 2)
        tickets = [srv.submit(x[:s]) for s in sizes]
        srv.pump(flush=True)
        for t, s in zip(tickets, sizes):
            np.testing.assert_allclose(np.asarray(srv.result(t)),
                                       np.asarray(ref(x[:s])), atol=1e-5)
        stats = srv.stats()
        assert stats["executed_batches"] < len(sizes)   # coalescing happened
        assert len(traced) == stats["misses"]           # hook saw every trace
        print("OK accel server on mesh")
    """))
