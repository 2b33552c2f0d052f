"""Sharding-rule resolution properties (pure logic — uses AbstractMesh, no
devices needed)."""
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import AbstractMesh, AxisType, PartitionSpec as P

from repro.configs import ARCHS
from repro.distributed.sharding import Param, Rules, resolve_spec, tree_specs
from repro.models.model import build_model


def mesh2(data=16, model=16):
    return AbstractMesh((data, model), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)


def mesh3():
    return AbstractMesh((2, 16, 16), ("pod", "data", "model"),
                        axis_types=(AxisType.Auto,) * 3)


def _spec_axes(spec):
    out = []
    for e in spec:
        if e is None:
            continue
        out.extend(e if isinstance(e, tuple) else (e,))
    return out


@settings(max_examples=100, deadline=None)
@given(dims=st.lists(st.sampled_from([1, 2, 3, 8, 24, 49155, 2048, 4096]),
                     min_size=1, max_size=4),
       names=st.lists(st.sampled_from(["batch", "fsdp", "tp", "vocab",
                                       "heads", "kv_seq", None]),
                      min_size=4, max_size=4))
def test_resolution_always_valid(dims, names):
    m = mesh3()
    sizes = dict(zip(m.axis_names, m.axis_sizes))
    spec = resolve_spec(dims, names[:len(dims)], m)
    used = _spec_axes(spec)
    # no mesh axis used twice
    assert len(used) == len(set(used))
    # divisibility always holds
    for dim, entry in zip(dims, spec):
        if entry is None:
            continue
        n = 1
        for a in (entry if isinstance(entry, tuple) else (entry,)):
            n *= sizes[a]
        assert dim % n == 0


def test_granite_vocab_fallback():
    """49155 % 16 != 0 -> vocab replicated, d_model picks up fsdp."""
    spec = resolve_spec((49155, 2048), ("vocab", "fsdp"), mesh2())
    assert spec == P(None, "data")


def test_divisible_vocab_gets_tp():
    spec = resolve_spec((163840, 2048), ("vocab", "fsdp"), mesh2())
    assert spec == P("model", "data")


def test_kv_cache_fallback_to_seq_sharding():
    # kv_heads=8 < model=16 -> heads replicated, cache seq gets model
    spec = resolve_spec((128, 32768, 8, 128),
                        ("batch", "kv_seq", "kv_heads", None), mesh2())
    assert spec == P("data", "model", None, None)


def test_batch_uses_pod_and_data_on_multipod():
    spec = resolve_spec((256, 4096), ("batch", None), mesh3())
    assert spec == P(("pod", "data"), None)


def test_fsdp_excludes_pod():
    """Params shard intra-pod only; cross-pod stays pure DP (compressible)."""
    spec = resolve_spec((4096, 8192), ("fsdp", "tp"), mesh3())
    assert spec == P("data", "model")


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_every_arch_resolves_on_both_meshes(arch):
    model = build_model(ARCHS[arch])
    tpl = model.template()
    for m in (mesh2(), mesh3()):
        specs = tree_specs(tpl, m)
        leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert leaves, arch
        params = jax.tree.leaves(tpl, is_leaf=lambda x: isinstance(x, Param))
        sizes = dict(zip(m.axis_names, m.axis_sizes))
        for p, spec in zip(params, leaves):
            for dim, entry in zip(p.shape, spec):
                if entry is None:
                    continue
                n = 1
                for a in (entry if isinstance(entry, tuple) else (entry,)):
                    n *= sizes[a]
                assert dim % n == 0, (arch, p.shape, spec)


def test_single_device_mesh_replicates_everything():
    m = AbstractMesh((1,), ("data",), axis_types=(AxisType.Auto,))
    spec = resolve_spec((64, 64), ("fsdp", "tp"), m)
    assert _spec_axes(spec) in ([], ["data"])  # data size 1 is harmless
