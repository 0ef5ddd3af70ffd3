"""The Fabric — ONE topology object from single-process to multi-host.

The paper's headline claim is DCRA as a *scale-out* compute node: packages
composed into larger systems over a software-configurable torus, with the
long-haul (die-NoC / DCN) hops concentrated at per-pod portals. Before
this module, every layer of the reproduction independently re-derived the
same topology facts from a raw ``jax.sharding.Mesh`` — axis-size dicts in
``sparse/program.py``, ``core/dispatch.py``, ``dse/autoconfig.py`` and
``launch/sharding.py``; mesh cache keys in the compile cache; pod/portal
detection in ``LaunchConfig.pod_axis_for`` — and all of it hard-assumed
one process.

:class:`Fabric` owns those facts in one frozen object:

* **construction** — :meth:`Fabric.single` (single-process),
  :meth:`Fabric.fake` (the ``xla_force_host_platform_device_count``
  subprocess rig every distributed test uses), and
  :meth:`Fabric.distributed` (multi-process ``jax.distributed`` — the
  leading mesh axis is process-major, so it is the axis whose collectives
  cross the data-center network);
* **introspection** — :attr:`axis_sizes` / :meth:`axis_size` (the single
  copy of the axis-size dict), :attr:`pod_axis` (portal derivation),
  :meth:`device_coords` (tile coordinates for the analytic models),
  :meth:`dcn_axes` (which axes actually cross processes);
* **identity** — :meth:`fabric_key`, the stable compile-cache key
  component, byte-compatible with the legacy ``_mesh_key`` so Fabric and
  raw-Mesh launches share cache entries;
* **scale-out** — :meth:`host_slice` (per-host ingest sharding, see
  :func:`repro.sparse.datasets.ingest_edges`) and :meth:`resize` (elastic
  rescale onto a changed device set, see :func:`repro.runtime.elastic`).

Raw meshes keep working everywhere through :func:`as_fabric` — the
warn-once deprecation shim the launch entrypoints funnel through —
and :meth:`Fabric.of`, the silent wrapper for query-only helpers.
"""
from __future__ import annotations

import math
import warnings
from dataclasses import dataclass, replace
from functools import cached_property
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from .compat import make_mesh
from .topology import TileGrid

#: conventional names of the axis that crosses pods / the DCN
PORTAL_AXIS_NAMES = ("pod", "portal")

_WARNED = [False]        # one-element list so tests can reset the latch


def _warn_mesh_once() -> None:
    if _WARNED[0]:
        return
    _WARNED[0] = True
    warnings.warn(
        "passing a raw Mesh to a DCRA launch entrypoint is deprecated: "
        "wrap it in a repro.core.fabric.Fabric (raw meshes keep working "
        "through this shim, with identical compile-cache keys)",
        DeprecationWarning, stacklevel=4)


@dataclass(frozen=True)
class Fabric:
    """Frozen topology of one DCRA deployment — the single source of
    truth for everything the layers used to re-derive from a raw mesh.

    ``mesh`` is the underlying ``jax.sharding.Mesh`` (duck-typed: any
    object with ``.devices`` / ``.axis_names`` works, which is what lets
    admission-only server tests run without a real device topology).
    ``portal_axis`` names the axis that crosses pods / the DCN; ``None``
    means a flat (single-pod) fabric. Construction never touches jax
    global state except :meth:`distributed` (which initializes
    ``jax.distributed`` exactly once).
    """
    mesh: Any
    portal_axis: Optional[str] = None

    # ---- construction ----------------------------------------------------

    @classmethod
    def of(cls, mesh_or_fabric) -> "Fabric":
        """Silent wrap for query-only helpers: a :class:`Fabric` passes
        through, a raw mesh is wrapped (portal axis auto-detected from
        :data:`PORTAL_AXIS_NAMES`) without the deprecation warning."""
        if isinstance(mesh_or_fabric, Fabric):
            return mesh_or_fabric
        names = tuple(getattr(mesh_or_fabric, "axis_names", ()) or ())
        portal = next((a for a in PORTAL_AXIS_NAMES if a in names), None)
        return cls(mesh=mesh_or_fabric, portal_axis=portal)

    @classmethod
    def single(cls, axis_shapes: Sequence[int], axis_names: Sequence[str],
               devices=None, portal_axis: Optional[str] = None) -> "Fabric":
        """Single-process fabric over the first ``prod(axis_shapes)``
        devices (the CPU-host-friendly ``compat.make_mesh`` path)."""
        mesh = make_mesh(tuple(axis_shapes), tuple(axis_names),
                         devices=devices)
        if portal_axis is None:
            portal_axis = next((a for a in PORTAL_AXIS_NAMES
                                if a in tuple(axis_names)), None)
        return cls(mesh=mesh, portal_axis=portal_axis)

    @classmethod
    def fake(cls, n_dev: int, axis: str = "data") -> "Fabric":
        """The fake-device subprocess rig fabric: a flat ``n_dev``-way
        fabric over host CPU devices. The process must have been started
        with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
        (N >= n_dev) *before* the first jax import — exactly the rig
        tests/benchmarks already use."""
        return cls.single((int(n_dev),), (axis,))

    @classmethod
    def distributed(cls, axis_shapes: Optional[Sequence[int]] = None,
                    axis_names: Optional[Sequence[str]] = None, *,
                    coordinator_address: Optional[str] = None,
                    num_processes: Optional[int] = None,
                    process_id: Optional[int] = None,
                    portal_axis: Optional[str] = None) -> "Fabric":
        """Multi-process fabric over ``jax.distributed``.

        Initializes ``jax.distributed`` (idempotent — an
        already-initialized runtime is reused) and builds one global mesh
        over every process's devices. ``jax.devices()`` orders devices
        process-major, so the **leading** mesh axis is the one whose
        groups span processes: declare the portal axis first
        (``axis_shapes=(n_proc, local)``, ``axis_names=("portal",
        "data")``) and the pod/portal stage-2 hop is the only traffic
        that crosses the DCN — the paper's §III-A hierarchy, for real.
        With no shape given, the fabric is flat: one ``data`` axis over
        all global devices (every all_to_all crosses the DCN).

        On the CPU backend the gloo collectives implementation is
        selected automatically (required for cross-process collectives on
        CPU; a no-op elsewhere).
        """
        import jax
        # must precede backend init; a no-op off the CPU backend
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        if coordinator_address is not None:
            try:
                jax.distributed.initialize(
                    coordinator_address=coordinator_address,
                    num_processes=num_processes, process_id=process_id)
            except RuntimeError:   # already initialized — reuse it
                pass
        devices = jax.devices()
        if axis_shapes is None:
            axis_shapes, axis_names = (len(devices),), ("data",)
        if axis_names is None:
            raise ValueError("axis_names is required with axis_shapes")
        return cls.single(axis_shapes, axis_names, devices=devices,
                          portal_axis=portal_axis)

    # ---- introspection (the deduped axis-size copies) --------------------

    @cached_property
    def axis_names(self) -> Tuple[str, ...]:
        return tuple(getattr(self.mesh, "axis_names", ()) or ())

    @cached_property
    def shape(self) -> Tuple[int, ...]:
        return tuple(int(s) for s in self.mesh.devices.shape)

    @cached_property
    def axis_sizes(self) -> Dict[str, int]:
        """``{axis name: size}`` — THE axis-size dict (previously copied
        privately by program/dispatch/autoconfig/sharding)."""
        return dict(zip(self.axis_names, self.shape))

    def axis_size(self, axes) -> int:
        """Product size of ``axes`` (None -> 1; a name; or a tuple of
        names — the ``MeshInfo.axis_size`` / ``sharding._axsize``
        contract, now in one place)."""
        if axes is None:
            return 1
        if isinstance(axes, str):
            axes = (axes,)
        return math.prod(self.axis_sizes[a] for a in axes)

    @property
    def n_devices(self) -> int:
        return int(self.mesh.devices.size)

    @cached_property
    def pod_axis(self) -> Optional[str]:
        """The portal axis when it can actually route across pods (size >
        1), else ``None`` — the mesh-introspection half of the old
        ``LaunchConfig.pod_axis_for``."""
        if self.portal_axis is None:
            return None
        if self.axis_sizes.get(self.portal_axis, 1) <= 1:
            return None
        return self.portal_axis

    # ---- identity: the compile-cache key ---------------------------------

    def fabric_key(self) -> tuple:
        """Stable identity for compile caches — byte-compatible with the
        legacy private ``_mesh_key(mesh)`` tuple, so a Fabric launch and
        a raw-Mesh launch of the same topology share ONE cache entry."""
        return (self.axis_names, self.shape,
                tuple(int(d.id) for d in self.mesh.devices.flat))

    # ---- multi-process topology ------------------------------------------

    @cached_property
    def process_indices(self) -> Tuple[int, ...]:
        """Sorted process indices owning this fabric's devices (``(0,)``
        for every single-process fabric, fake rigs included)."""
        try:
            procs = {int(d.process_index) for d in self.mesh.devices.flat}
        except AttributeError:          # duck-typed mesh (tests)
            procs = {0}
        return tuple(sorted(procs)) or (0,)

    @property
    def n_processes(self) -> int:
        return len(self.process_indices)

    @property
    def is_multiprocess(self) -> bool:
        return self.n_processes > 1

    @cached_property
    def process_index(self) -> int:
        """This process's rank within the fabric (0 single-process)."""
        if not self.is_multiprocess:
            return 0
        import jax
        return self.process_indices.index(int(jax.process_index()))

    def dcn_axes(self) -> Tuple[str, ...]:
        """Mesh axes along which neighboring devices live in *different*
        processes — the axes whose collectives cross the DCN. Empty for
        every single-process fabric."""
        if not self.is_multiprocess:
            return ()
        procs = np.array([[int(d.process_index)]
                          for d in self.mesh.devices.flat]
                         ).reshape(self.shape)
        out = []
        for i, name in enumerate(self.axis_names):
            if self.shape[i] > 1 and bool(
                    (np.diff(procs, axis=i) != 0).any()):
                out.append(name)
        return tuple(out)

    def host_slice(self, total: int, *, rank: Optional[int] = None,
                   world: Optional[int] = None) -> Tuple[int, int]:
        """This host's contiguous ``[lo, hi)`` slice of ``total`` ingest
        items (edge chunks, dataset rows): a balanced split over the
        fabric's processes, so no host ever materializes the full list.
        ``rank`` / ``world`` override the fabric's own process info (the
        single-process tests simulate multi-host splits with them)."""
        world = self.n_processes if world is None else int(world)
        rank = self.process_index if rank is None else int(rank)
        if not 0 <= rank < world:
            raise ValueError(f"rank {rank} outside world {world}")
        base, rem = divmod(int(total), world)
        lo = rank * base + min(rank, rem)
        return lo, lo + base + (1 if rank < rem else 0)

    # ---- analytic-model hooks --------------------------------------------

    def tile_grid(self) -> TileGrid:
        """The analytic-twin grid at this fabric's parallelism: one tile
        per shard (``TileGrid(1, n_devices)``), the channel structure the
        shardcheck revalidation relies on."""
        return TileGrid(1, self.n_devices)

    def device_coords(self) -> Tuple[Tuple[int, Tuple[int, ...]], ...]:
        """``((device_id, mesh coordinates), ...)`` in mesh order — tile
        coordinates for the analytic cost models and placement checks."""
        return tuple((int(d.id), tuple(int(c) for c in idx))
                     for idx, d in np.ndenumerate(self.mesh.devices))

    # ---- elasticity ------------------------------------------------------

    def resize(self, devices=None) -> "Fabric":
        """A new fabric over a *changed* device set (defaults to every
        currently-live ``jax.devices()``) — the elastic-rescale hook.

        Keeps the trailing (intra-pod) axis structure and lets the
        leading (host/DCN-crossing) axis absorb the change; when the new
        device count cannot keep that structure, degrades to a flat
        fabric over the last axis name. Pair with
        :func:`repro.runtime.elastic.rescale`: a lost host degrades
        capacity instead of killing the run.
        """
        if devices is None:
            import jax
            devices = jax.devices()
        devs = np.asarray(list(devices))
        if devs.size == 0:
            raise ValueError("cannot resize to an empty device set")
        inner = math.prod(self.shape[1:]) if len(self.shape) > 1 else 1
        lead, rem = divmod(devs.size, inner)
        if len(self.shape) > 1 and rem == 0 and lead >= 1:
            new_shape: Tuple[int, ...] = (lead,) + self.shape[1:]
            new_names = self.axis_names
        else:
            new_shape = (int(devs.size),)
            new_names = self.axis_names[-1:] or ("data",)
        import jax.sharding as jsh
        mesh = jsh.Mesh(devs.reshape(new_shape), new_names)
        portal = (self.portal_axis if self.portal_axis in new_names
                  else None)
        return replace(self, mesh=mesh, portal_axis=portal)

    def shrink(self, keep: int) -> "Fabric":
        """:meth:`resize` onto the first ``keep`` devices of THIS fabric
        (mesh order) — the host-loss degrade: the survivors are a prefix
        of the current device set, no fresh ``jax.devices()`` query (a
        lost host's devices may still be enumerable but unusable).
        ``ProgramServer`` calls this on an injected
        ``host_loss`` fault; a new ``fabric_key()`` means relaunched
        shape classes re-trace on the shrunken fabric by construction.
        """
        keep = int(keep)
        if not 1 <= keep <= self.n_devices:
            raise ValueError(f"shrink keeps {keep} of {self.n_devices} "
                             f"devices — need 1 <= keep <= n_devices")
        return self.resize(list(self.mesh.devices.flat)[:keep])


def axis_sizes_of(mesh_or_fabric) -> Dict[str, int]:
    """The one shared axis-size dict accessor (module-level sugar for
    call sites that hold a raw mesh)."""
    return Fabric.of(mesh_or_fabric).axis_sizes


def as_fabric(mesh_or_fabric) -> Fabric:
    """THE launch-entrypoint shim: a :class:`Fabric` passes through; a
    raw mesh is wrapped with a one-time :class:`DeprecationWarning` (same
    latch pattern as the LaunchOptions legacy-kwarg shim). Cache keys are
    identical either way (:meth:`Fabric.fabric_key`)."""
    if isinstance(mesh_or_fabric, Fabric):
        return mesh_or_fabric
    _warn_mesh_once()
    return Fabric.of(mesh_or_fabric)
