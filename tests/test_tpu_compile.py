"""Ahead-of-time compiles of the main path for a TPU v5e.

The TPU compiler ships with jaxlib, so it compiles for a described v5e
topology with no chip attached. Interpret-mode tests cannot see what
Mosaic refuses (unsupported primitives, layouts, scalar stores); these
compiles can. Each kernel test asserts the kernel really is in the
program (``tpu_custom_call``), so a silent switch to an XLA rendering
fails. The NoC round tests assert that the wire crosses the chips as
int32: the TPU compiler may lower a float concatenate through a float
``maximum``, which rewrites NaN and denormal bit patterns, so bitcast
ints would not survive an f32 wire.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test runner's workers all import every test file.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.compat import make_mesh, shard_map_unchecked
from repro.core.routing import (owner_route, owner_route_hier,
                                owner_route_hier_start)
from repro.kernels.histogram import histogram_pallas
from repro.kernels.route import bucket_rank_pallas

N = 1 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


# precision "highest" is what the MoE check runs under: the kernel's bf16
# count matmuls must not inherit it (Mosaic refuses an f32 contraction)
@pytest.mark.parametrize("n_buckets,precision",
                         [(1, None), (4, None), (128, None), (64, "highest")])
def test_rank_kernel_compiles(one_chip, no_persistent_cache, n_buckets,
                              precision):
    dest = jax.ShapeDtypeStruct((N,), jnp.int32, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((N,), jnp.bool_, sharding=one_chip)
    with jax.default_matmul_precision(precision):
        text = _compiled_text(
            lambda d, v: bucket_rank_pallas(d, v, n_buckets,
                                            interpret=False),
            dest, valid)
    assert "tpu_custom_call" in text


def test_histogram_kernel_compiles(one_chip, no_persistent_cache):
    elems = jax.ShapeDtypeStruct((N,), jnp.int32, sharding=one_chip)
    text = _compiled_text(
        lambda e: histogram_pallas(e, 4096, interpret=False), elems)
    assert "tpu_custom_call" in text


def _flat(v, s, o, ok):
    return owner_route(v, s, o, ok, 4, 1024, "data")[:2]


def _hier(v, s, o, ok):
    return owner_route_hier(v, s, o, ok, 2, "data", 2, "pod", 2048,
                            4096)[:2]


def _hier_pipelined(v, s, o, ok):
    recv, _, _, gsignal = owner_route_hier_start(
        v, s, o, ok, 2, "data", 2, "pod", 2048, 4096, jnp.sum(ok))
    return recv, gsignal[None]


@pytest.mark.parametrize("route", [_flat, _hier, _hier_pipelined],
                         ids=["flat", "hier", "hier_pipelined"])
def test_noc_round_wire_is_int32(topo, no_persistent_cache, route):
    mesh = make_mesh((2, 2), ("pod", "data"), devices=topo.devices)
    spec = P(("pod", "data"))
    kernel = jax.jit(shard_map_unchecked(route, mesh, (spec,) * 4,
                                         (spec, spec)))
    n = 4 * 4096
    shard = NamedSharding(mesh, spec)
    args = [jax.ShapeDtypeStruct((n,), dt, sharding=shard)
            for dt in (jnp.float32, jnp.int32, jnp.int32, jnp.bool_)]
    text = kernel.lower(*args).compile().as_text()
    a2a = [ln for ln in text.splitlines() if " all-to-all(" in ln]
    assert a2a
    assert all("= s32[" in ln for ln in a2a), a2a
