"""Thin wrappers over the jax 0.9 API surface (see requirements-dev.txt)
that every launch path shares:

* ``shard_map_unchecked`` — ``jax.shard_map`` with the VMA checker off;
* ``make_mesh`` — a ``jax.sharding.Mesh`` over the *first* devices, so a
  mesh may cover a subset of the process's devices (the routing property
  tier runs 1/2/4/8-device meshes inside one 8-device process);
* ``set_mesh`` — ``jax.set_mesh``;
* ``cost_analysis`` — ``compiled.cost_analysis()`` as a dict;
* ``use_compile_cache`` — where entry points keep JAX's persistent
  compilation cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax
import numpy as np

#: the persistent compile cache's home when ``JAX_COMPILATION_CACHE_DIR``
#: is unset: a fixed path inside the checkout (the path is part of the
#: cache key, so it must not move between runs)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def shard_map_unchecked(fn, mesh, in_specs, out_specs):
    """shard_map with VMA checking off (collective-heavy kernels trip the
    static checker)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(axis_shapes, axis_names, devices=None):
    """A Mesh over the first prod(axis_shapes) devices (CPU-host friendly)."""
    if devices is None:
        devices = jax.devices()
    n = int(np.prod(axis_shapes))
    if len(devices) < n:
        raise ValueError(f"need {n} devices, have {len(devices)}")
    arr = np.asarray(devices[:n]).reshape(axis_shapes)
    return jax.sharding.Mesh(arr, axis_names)


def set_mesh(mesh):
    """Context manager scoping ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


def cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` (empty dict when XLA reports none)."""
    return compiled.cost_analysis() or {}


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing else is configured here. Otherwise the cache goes to
    :data:`REPO_CACHE_DIR`. Call before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
