"""LaunchOptions — ONE object for every launch-configuration kwarg.

The seven ``dcra_*`` apps, :func:`repro.sparse.program.run_program` and
:func:`repro.sparse.program.dcra_scatter` historically each re-declared
the same 9-kwarg sprawl (``axis``, ``pod_axis``, ``cap``,
``capacity_factor``, ``queues``, ``config``, ``objective``, ``seed``,
``route_impl`` — and now ``round_mode``), with the cross-kwarg conflict
rules scattered across them. :class:`LaunchOptions` collapses that into
one frozen dataclass whose :meth:`LaunchOptions.resolve` owns ALL the
conflict checks in exactly one place; every entrypoint accepts
``options=``, and the legacy kwargs keep working through
:func:`resolve_options` — a shim that forwards them into a
``LaunchOptions`` and emits a one-time :class:`DeprecationWarning`.

    opts = LaunchOptions(capacity_factor=4.0, route_impl="sort",
                         round_mode="pipelined")
    dist, stats = dcra_bfs(g, 0, mesh, options=opts)

Migration table (old kwarg -> field) is in the README.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, fields, replace
from typing import Any, Optional

from ..core.queues import QueueConfig

ROUND_MODES = ("lockstep", "pipelined")

# legacy kwargs whose "unset" sentinel is a real value, not None — an
# explicitly passed default is indistinguishable from unset, which is
# exactly the old behavior the shim preserves
_NON_NONE_DEFAULTS = {"axis": "data", "objective": "teps", "seed": 0}

_WARNED = [False]        # one-element list so tests can reset the latch


@dataclass(frozen=True)
class LaunchOptions:
    """Every launch-configuration knob of one DCRA launch, in one place.

    ``axis`` / ``pod_axis`` name the mesh axes (``pod_axis`` selects the
    hierarchical pod/portal routing path); exactly one of ``queues`` /
    ``cap`` / ``capacity_factor`` may size the IQs (or ``config`` — a
    LaunchConfig, DesignPoint or ``"auto"`` — may own sizing entirely);
    ``objective`` steers ``config="auto"``; ``seed`` fixes the edge-pack
    shuffle; ``route_impl`` picks the routing hot-path engine ("pallas" |
    "sort" | "onehot" | None = autodetect); ``round_mode`` picks the round
    execution shape ("lockstep" | "pipelined" — bit-identical results,
    see README "Pipelined rounds"). It shapes multi-device rounds only: a
    one-device flat launch has no wire to overlap, so both modes run the
    same round, with the receive-reduce folded into admission.
    """
    axis: str = "data"
    pod_axis: Optional[str] = None
    cap: Optional[int] = None
    capacity_factor: Optional[float] = None
    queues: Optional[QueueConfig] = None
    config: Any = None
    objective: str = "teps"
    seed: int = 0
    route_impl: Optional[str] = None
    round_mode: str = "lockstep"

    def resolve(self) -> "LaunchOptions":
        """Validate cross-field consistency — THE single conflict-check
        path every entrypoint funnels through (legacy kwargs included,
        via :func:`resolve_options`). Returns ``self`` so call sites can
        chain; raises ``ValueError`` on any conflict."""
        sizing = tuple(name for name, v in
                       (("queues", self.queues), ("cap", self.cap),
                        ("capacity_factor", self.capacity_factor))
                       if v is not None)
        if len(sizing) > 1:
            raise ValueError(f"{sizing[0]}= conflicts with explicit "
                             f"{sizing[1:]}: IQ sizing resolves through "
                             f"exactly one of queues/cap/capacity_factor")
        if self.config is not None and sizing:
            raise ValueError(f"config= conflicts with explicit {sizing}: "
                             f"queue sizing comes from the resolved "
                             f"LaunchConfig, drop one of them")
        if self.round_mode not in ROUND_MODES:
            raise ValueError(f"unknown round_mode {self.round_mode!r} "
                             f"(expected one of {ROUND_MODES})")
        if self.route_impl is not None:
            from ..kernels.route import resolve_route_impl
            resolve_route_impl(self.route_impl)      # raises on unknown
        return self

    def with_(self, **changes) -> "LaunchOptions":
        """Functional update (dataclasses.replace sugar)."""
        return replace(self, **changes)


_FIELD_NAMES = tuple(f.name for f in fields(LaunchOptions))


def _warn_legacy(names) -> None:
    if _WARNED[0]:
        return
    _WARNED[0] = True
    warnings.warn(
        f"launch kwargs {tuple(names)} are deprecated: pass "
        f"options=LaunchOptions(...) instead (the legacy kwargs keep "
        f"working through this shim)", DeprecationWarning, stacklevel=4)


def resolve_options(options: Optional[LaunchOptions] = None,
                    **legacy) -> LaunchOptions:
    """The legacy-kwarg shim every entrypoint funnels through.

    With ``options=`` set, every legacy kwarg must be at its default —
    mixing the two styles raises rather than guessing precedence. With
    legacy kwargs only, they are forwarded into a :class:`LaunchOptions`
    (one ``DeprecationWarning`` per process, the first time any
    non-default legacy kwarg is seen). Either way the result is
    :meth:`LaunchOptions.resolve`-d, so both styles hit the identical
    conflict checks — and produce identical compile-cache keys.
    """
    unknown = [k for k in legacy if k not in _FIELD_NAMES]
    if unknown:
        raise TypeError(f"unknown launch kwargs {unknown}")
    explicit = {k: v for k, v in legacy.items()
                if v is not None and v != _NON_NONE_DEFAULTS.get(k)}
    if options is not None:
        if not isinstance(options, LaunchOptions):
            raise TypeError(f"options= expects a LaunchOptions, got "
                            f"{type(options).__name__}")
        if explicit:
            raise ValueError(f"options= conflicts with explicit legacy "
                             f"kwargs {tuple(sorted(explicit))}: fold "
                             f"them into the LaunchOptions")
        return options.resolve()
    if explicit:
        _warn_legacy(sorted(explicit))
    return LaunchOptions(**{k: v for k, v in legacy.items()
                            if v is not None}).resolve()
