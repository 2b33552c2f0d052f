"""The ranking kernels compiled for a described (not attached) TPU v5e.

Interpret mode cannot see Mosaic's block-shape and memory rules; these
compiles can.  Each test lowers one ``maizx_rank`` entry point at
region scale (N = 1,048,576 nodes, shortlist k = 33, L = 4 ensemble lanes)
with ``interpret=False`` and checks that the kernel is in the program as a
``tpu_custom_call``.  Nothing runs, so results and times are not checked.

The topology is described inside a fixture: only the worker that runs
these tests loads the TPU compiler, and every worker collects the same
tests."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.maizx_rank import (maiz_lohi_pallas, maiz_lohi_pallas_b,
                                      maiz_topk_pallas, maiz_topk_pallas_b)

N = 1_048_576
K = 33
L = 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    # a compile for a described chip is written to the cache but cannot be
    # read back without one; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _operands(sharding, lanes, marginal, room=False):
    """(node streams, n_valid, lohi, weights, marginal and room kwargs) as
    shapes; with the marginal streams ``cap`` is the room."""
    node = (N,) if lanes is None else (lanes, N)
    lead = () if lanes is None else (lanes,)
    r = 5 if marginal else 4
    f32 = lambda s: _sds(s, jnp.float32, sharding)
    streams = [f32(node) for _ in range(6)]
    mkw = {}
    if marginal:
        mkw = dict(pk=f32(node), cap=f32(node), ct=f32(node),
                   en=f32(lead + (4,)))
    if room:
        mkw.update(room_min=f32(lead))
        if not marginal:
            mkw.update(room=f32(node))
    return (streams, _sds((1, 1), jnp.int32, sharding), f32(lead + (r, 2)),
            f32((4,)), mkw)


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("marginal", [False, True], ids=["4term", "5term"])
def test_lohi_compiles(one_chip, marginal):
    streams, n_valid, _, _, mkw = _operands(one_chip, None, marginal)
    _assert_kernel(maiz_lohi_pallas.lower(*streams, n_valid,
                                          interpret=False, **mkw))


# the room threshold widens sweep 2's mask only
TOPK_CASES = dict(argvalues=[(False, False), (True, False),
                             (False, True), (True, True)],
                  ids=["4term", "5term", "4term-room", "5term-room"])


@pytest.mark.parametrize("marginal,room", **TOPK_CASES)
def test_topk_compiles(one_chip, marginal, room):
    streams, n_valid, lohi, w, mkw = _operands(one_chip, None, marginal,
                                               room)
    _assert_kernel(maiz_topk_pallas.lower(*streams, n_valid, lohi, w, k=K,
                                          interpret=False, **mkw))


@pytest.mark.parametrize("marginal", [False, True], ids=["4term", "5term"])
def test_lohi_batched_compiles(one_chip, marginal):
    streams, n_valid, _, _, mkw = _operands(one_chip, L, marginal)
    _assert_kernel(maiz_lohi_pallas_b.lower(*streams, n_valid,
                                            interpret=False, **mkw))


@pytest.mark.parametrize("marginal,room", **TOPK_CASES)
def test_topk_batched_compiles(one_chip, marginal, room):
    streams, n_valid, lohi, w, mkw = _operands(one_chip, L, marginal, room)
    _assert_kernel(maiz_topk_pallas_b.lower(*streams, n_valid, lohi, w, k=K,
                                            interpret=False, **mkw))
