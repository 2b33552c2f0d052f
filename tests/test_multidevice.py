"""Multi-device behaviours (8 forced host devices) — run in SUBPROCESSES so
the XLA device-count flag never leaks into the other tests (the brief
requires smoke tests to see 1 device)."""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(body: str) -> str:
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding, AxisType
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=540)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_int8_compressed_psum_accuracy_and_wire_format():
    out = run_sub("""
        from repro.train.compression import compressed_psum_mean, psum_mean
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(AxisType.Auto,)*3)
        rng = np.random.default_rng(0)
        g_local = jnp.asarray(rng.standard_normal((2, 64, 32)), jnp.float32)

        def sync(kind):
            def f(g):
                fn = compressed_psum_mean if kind == "int8" else psum_mean
                return fn({"g": g}, "pod")["g"]
            return jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
                axis_names={"pod"}, check_vma=False))

        exact = sync("fp32")(g_local)
        approx = sync("int8")(g_local)
        err = float(jnp.max(jnp.abs(exact - approx)))
        bound = float(jnp.max(jnp.abs(g_local))) / 127.0  # per-pod scale err
        assert err <= bound + 1e-6, (err, bound)
        # wire format: the big collective must be int8 (all-gather), not f32
        txt = sync("int8").lower(g_local).compile().as_text()
        assert "s8[" in txt and "all-gather" in txt, txt[:2000]
        print("OK")
    """)
    assert "OK" in out


def test_int16_psum_sync_halves_wire_and_stays_accurate():
    out = run_sub("""
        from repro.train.compression import int16_psum_mean, psum_mean
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(AxisType.Auto,)*3)
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.standard_normal((2, 64, 32)), jnp.float32)

        def sync(fn):
            return jax.jit(jax.shard_map(
                lambda x: fn({"g": x}, "pod")["g"], mesh=mesh,
                in_specs=P("pod"), out_specs=P("pod"),
                axis_names={"pod"}, check_vma=False))

        exact = sync(psum_mean)(g)
        approx = sync(int16_psum_mean)(g)
        err = float(jnp.max(jnp.abs(exact - approx)))
        bound = float(jnp.max(jnp.abs(g))) / 127.0
        assert err <= bound + 1e-6, (err, bound)
        txt = sync(int16_psum_mean).lower(g).compile().as_text()
        assert "s16[" in txt, txt[:1500]
        print("OK")
    """)
    assert "OK" in out


def test_checkpoint_restores_across_mesh_shapes():
    out = run_sub("""
        import tempfile
        from repro.train import checkpoint as ckpt
        from repro.distributed.sharding import Param, tree_shardings
        tmp = tempfile.mkdtemp()
        tpl = {"w": Param((8, 16), ("fsdp", "tp"))}
        m1 = jax.make_mesh((4, 2), ("data", "model"),
                           axis_types=(AxisType.Auto,)*2)
        m2 = jax.make_mesh((2, 4), ("data", "model"),
                           axis_types=(AxisType.Auto,)*2)
        sh1 = tree_shardings(tpl, m1)
        sh2 = tree_shardings(tpl, m2)
        w = jnp.arange(128.0, dtype=jnp.bfloat16).reshape(8, 16)
        state = {"w": jax.device_put(w, sh1["w"])}
        ckpt.save(tmp, state, 3)
        restored, step, _ = ckpt.restore(tmp, state, sh2)
        assert step == 3
        np.testing.assert_array_equal(np.asarray(restored["w"], np.float32),
                                      np.asarray(w, np.float32))
        assert restored["w"].sharding == sh2["w"]
        print("OK")
    """)
    assert "OK" in out


def test_train_step_parity_across_meshes():
    """One train step on (1,1) vs (2,2) vs (2,2,2) meshes: same loss/params
    (the data pipeline + sharding rules promise mesh-shape independence)."""
    out = run_sub("""
        from repro.configs import ARCHS
        from repro.models.model import ModelFlags, build_model
        from repro.train.optimizer import AdamWConfig
        from repro.train.train_step import TrainState, make_train_step
        from repro.distributed.sharding import tree_shardings, Param
        from repro.data.pipeline import DataConfig, PipelineState, host_batch

        cfg = ARCHS["granite-3-2b"].reduced()
        model = build_model(cfg, ModelFlags(attn_chunk=32))
        dcfg = DataConfig(cfg, batch=8, seq=32, task="copy")
        _, batch_np = host_batch(dcfg, PipelineState(0, 0))

        results = []
        meshes = [
            jax.make_mesh((1, 1), ("data", "model"),
                          axis_types=(AxisType.Auto,)*2),
            jax.make_mesh((2, 2), ("data", "model"),
                          axis_types=(AxisType.Auto,)*2),
            jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                          axis_types=(AxisType.Auto,)*3),
        ]
        for mesh in meshes:
            sh = tree_shardings(model.template(), mesh)
            params = jax.device_put(model.init(jax.random.key(0)), sh)
            state = TrainState.create(params)
            step = jax.jit(make_train_step(model, AdamWConfig()))
            batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
            state, metrics = step(state, batch)
            results.append((float(metrics["loss"]),
                            float(metrics["grad_norm"])))
        for r in results[1:]:
            assert abs(r[0] - results[0][0]) < 5e-3, results
            assert abs(r[1] - results[0][1]) / results[0][1] < 5e-2, results
        print("OK", results)
    """)
    assert "OK" in out


def test_int8_grad_sync_trains_equivalently():
    out = run_sub("""
        from repro.configs import ARCHS
        from repro.models.model import ModelFlags, build_model
        from repro.train.optimizer import AdamWConfig
        from repro.train.train_step import TrainState, make_train_step
        from repro.distributed.sharding import tree_shardings
        from repro.data.pipeline import DataConfig, PipelineState, host_batch

        cfg = ARCHS["granite-3-2b"].reduced()
        model = build_model(cfg, ModelFlags(attn_chunk=32))
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(AxisType.Auto,)*3)
        dcfg = DataConfig(cfg, batch=8, seq=32, task="copy")
        _, batch_np = host_batch(dcfg, PipelineState(0, 0))
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
        sh = tree_shardings(model.template(), mesh)
        losses = {}
        for sync in ("auto", "int8"):
            params = jax.device_put(model.init(jax.random.key(0)), sh)
            state = TrainState.create(params)
            fn = jax.jit(make_train_step(model, AdamWConfig(),
                                         grad_sync=sync, mesh=mesh))
            for _ in range(3):
                state, metrics = fn(state, batch)
            losses[sync] = float(metrics["loss"])
        assert abs(losses["auto"] - losses["int8"]) < 5e-2, losses
        print("OK", losses)
    """)
    assert "OK" in out
