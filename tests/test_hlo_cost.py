"""The loop-aware HLO cost parser vs ground truth (subprocess: needs a
multi-device mesh for collective tests)."""
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
        from repro.launch.hlo_cost import analyze
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=540)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_parser_matches_xla_on_loop_free_graph():
    out = run_sub("""
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,)*2)
        S = lambda *s: NamedSharding(mesh, P(*s))
        def f(x, w1, w2):
            return jnp.tanh(x @ w1) @ w2
        args = (jax.ShapeDtypeStruct((256, 512), jnp.bfloat16,
                                     sharding=S("data", None)),
                jax.ShapeDtypeStruct((512, 1024), jnp.bfloat16,
                                     sharding=S(None, "model")),
                jax.ShapeDtypeStruct((1024, 512), jnp.bfloat16,
                                     sharding=S("model", None)))
        c = jax.jit(f).lower(*args).compile()
        got = analyze(c.as_text())
        xla = c.cost_analysis()["flops"]
        assert abs(got.flops - xla) / xla < 0.05, (got.flops, xla)
        assert got.coll_per_kind.get("all-reduce", 0) > 0
        print("OK")
    """)
    assert "OK" in out


def test_parser_scales_loop_bodies_by_trip_count():
    out = run_sub("""
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,)*2)
        S = lambda *s: NamedSharding(mesh, P(*s))
        def f(x, ws):
            def body(h, w):
                return jnp.tanh(h @ w), None
            h, _ = jax.lax.scan(body, x, ws)
            return h
        args = (jax.ShapeDtypeStruct((256, 512), jnp.bfloat16,
                                     sharding=S("data", None)),
                jax.ShapeDtypeStruct((12, 512, 512), jnp.bfloat16,
                                     sharding=S(None, None, "model")))
        c = jax.jit(f).lower(*args).compile()
        got = analyze(c.as_text())
        expected = 12 * 2 * 256 * 512 * 512 / 8     # per-device dot flops
        assert abs(got.flops - expected) / expected < 0.10, got.flops
        # the in-loop weight all-gather must be scaled by 12 too
        ag = got.coll_per_kind.get("all-gather", 0)
        assert ag >= 12 * (512 * 512 * 2 / 8), ag
        print("OK")
    """)
    assert "OK" in out


def test_shape_and_collective_regexes():
    from repro.launch.hlo_cost import _shape_elems_bytes
    elems, bts = _shape_elems_bytes("bf16[4,8]{1,0}")
    assert elems == 32 and bts == 64
    elems, bts = _shape_elems_bytes("(f32[2,2]{1,0}, s8[16]{0})")
    assert elems == 20 and bts == 32
    elems, bts = _shape_elems_bytes("f32[]")
    assert elems == 1 and bts == 4  # scalar
