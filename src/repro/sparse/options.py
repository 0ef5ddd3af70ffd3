"""LaunchOptions — ONE object for every launch setting.

:func:`repro.sparse.program.run_program`,
:func:`repro.sparse.program.launch_program`,
:func:`repro.sparse.program.dcra_scatter`, the seven ``dcra_*`` apps and
:class:`repro.serve.engine.ProgramServer` take their launch settings as
``options=``: one frozen dataclass whose :meth:`LaunchOptions.resolve`
owns ALL the cross-field conflict checks in exactly one place.

    opts = LaunchOptions(capacity_factor=4.0, round_mode="pipelined")
    dist, stats = dcra_bfs(g, 0, fabric, options=opts)
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Optional

from ..core.queues import QueueConfig

ROUND_MODES = ("lockstep", "pipelined")


@dataclass(frozen=True)
class LaunchOptions:
    """Every launch setting of one DCRA launch, in one place.

    ``axis`` / ``pod_axis`` name the mesh axes (``pod_axis`` selects the
    hierarchical pod/portal routing path); exactly one of ``queues`` /
    ``cap`` / ``capacity_factor`` may size the IQs (or ``config`` — a
    LaunchConfig, DesignPoint or ``"auto"`` — may own sizing entirely);
    ``objective`` steers ``config="auto"``; ``seed`` fixes the edge-pack
    shuffle; ``round_mode`` picks the round execution shape
    ("lockstep" | "pipelined" — bit-identical results, see README
    "Pipelined rounds"). It shapes multi-device rounds only: a one-device
    flat launch has no wire to overlap, so both modes run the same round,
    with the receive-reduce folded into admission.
    """
    axis: str = "data"
    pod_axis: Optional[str] = None
    cap: Optional[int] = None
    capacity_factor: Optional[float] = None
    queues: Optional[QueueConfig] = None
    config: Any = None
    objective: str = "teps"
    seed: int = 0
    round_mode: str = "lockstep"

    def resolve(self) -> "LaunchOptions":
        """Validate cross-field consistency — THE single conflict-check
        path every entrypoint funnels through. Returns ``self`` so call
        sites can chain; raises ``ValueError`` on any conflict."""
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
        return self

    def with_(self, **changes) -> "LaunchOptions":
        """Functional update (dataclasses.replace sugar)."""
        return replace(self, **changes)
