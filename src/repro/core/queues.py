"""Task queues (IQ/OQ) — DCRA Table II knob #8. THE single source of
queue-capacity truth.

Each task type has an input queue (IQ) at the consumer tile and an output
queue (OQ) at the producer. The engine records per-round occupancies; the
performance model converts overflow into producer stalls (the paper's
Fig. 10 mechanism: undersized OQ2 stalls the upstream task at high fanout).

Since PR 3 every bounded-queue capacity in the repo resolves through
:class:`QueueConfig` — there is no ``TaskEngine(iq_capacity=...)`` /
``route(iq_capacity=...)`` side-channel any more:

* the analytic :meth:`repro.core.task_engine.TaskEngine.route` reads
  ``cfg.queues.iq(task)`` per task type (``None`` = unbounded legacy
  stats, via :meth:`QueueConfig.unbounded`);
* the executable routing layer (``dcra_scatter`` and the MoE dispatch)
  resolves per-round bucket capacities with :meth:`QueueConfig.channel_cap`
  — either an explicit entry count (the DSE IQ axis, honored exactly) or a
  relative *capacity factor* (``iq_factors``; the MoE dispatch knob),
  lane-aligned with :func:`round8`.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional

import numpy as np


def round8(x: int) -> int:
    """Round a capacity up to a multiple of 8 (TPU lane alignment)."""
    return max(8, -(-x // 8) * 8)


# The MoE dispatch's bounded-queue task names (see for_moe_dispatch).
MOE_DISPATCH_TASKS = ("dispatch", "portal", "expert")


@dataclass
class QueueConfig:
    iq_sizes: Dict[str, int] = field(default_factory=dict)
    oq_sizes: Dict[str, int] = field(default_factory=dict)
    default_iq: Optional[int] = 12  # task-invocation messages (paper Fig. 10)
    default_oq: int = 12
    # Relative sizing: capacity = tasks_per_round * factor / n_channels
    # (the MoE "capacity factor" IS the IQ axis — ROADMAP fold-in). An
    # explicit per-task entry in ``iq_sizes`` always wins over a factor.
    iq_factors: Dict[str, float] = field(default_factory=dict)

    def iq(self, task: str) -> Optional[int]:
        """Explicit per-channel IQ capacity for ``task`` (None =
        unbounded). Factor-sized tasks (``iq_factors``) have no fixed
        entry count — resolve those per round with :meth:`channel_cap`,
        which is what ``TaskEngine.route`` and the executables both use,
        so the two paths can't disagree."""
        return self.iq_sizes.get(task, self.default_iq)

    def oq(self, task: str) -> int:
        return self.oq_sizes.get(task, self.default_oq)

    def channel_cap(self, task: str, tasks_per_round: int,
                    n_channels: int, lane_align: bool = True
                    ) -> Optional[int]:
        """Resolve one routing round's per-channel bucket capacity.

        Explicit sizes (``iq_sizes`` / ``default_iq``) are honored exactly
        — the DSE revalidation sweeps the IQ axis in queue entries, so
        rounding would validate a different capacity than the analytic
        model swept. Factor-derived capacities (``iq_factors``) are
        lane-aligned via :func:`round8` unless ``lane_align=False``.
        Returns ``None`` when the task's queue is unbounded.
        """
        explicit = self.iq_sizes.get(task)
        if explicit is None and task not in self.iq_factors:
            explicit = self.default_iq
        if explicit is not None:
            return max(1, int(explicit))
        factor = self.iq_factors.get(task)
        if factor is None:
            return None
        cap = int(tasks_per_round * factor / max(n_channels, 1))
        return round8(cap) if lane_align else max(1, cap)

    def round_budget(self, task: str, tasks_per_round: int,
                     n_channels: int) -> Optional[int]:
        """Total per-round admission budget for ``task``: the per-channel
        IQ capacity times the channel count — what a whole tenant may
        inject into the NoC in one round before overflowing its queues.

        This is the serving tier's admission-control knob
        (:class:`repro.serve.engine.ProgramServer`): a request whose
        estimated per-round task demand exceeds its tenant's budget is
        rejected with a retriable status *before* launch, instead of
        silently dropping tasks in flight. ``None`` = unbounded (no
        admission limit).
        """
        cap = self.channel_cap(task, tasks_per_round, n_channels)
        return None if cap is None else int(cap) * max(1, n_channels)

    @classmethod
    def unbounded(cls) -> "QueueConfig":
        """Legacy physics: no IQ bound, no modeled drops."""
        return cls(default_iq=None)

    @classmethod
    def from_factor(cls, factor: float, task: str = "T3") -> "QueueConfig":
        """Relative sizing only (the MoE-style capacity-factor knob)."""
        return cls(default_iq=None, iq_factors={task: factor})

    @classmethod
    def from_cap(cls, cap: int, task: str = "T3") -> "QueueConfig":
        """One explicit per-channel capacity, honored exactly."""
        return cls(default_iq=None, iq_sizes={task: int(cap)})

    @classmethod
    def for_moe_dispatch(cls, factor: float) -> "QueueConfig":
        """The MoE dispatch's three bounded buckets — stage-1 tile-NoC
        ("dispatch"), stage-2 pod portal ("portal"), per-local-expert
        receive ("expert") — at one capacity factor. The single home of
        those bucket names: ``repro.core.dispatch.dispatch_queues`` and
        ``DesignPoint.moe_queues`` both delegate here."""
        return cls(default_iq=None,
                   iq_factors={t: factor for t in MOE_DISPATCH_TASKS})


@dataclass
class QueueStats:
    """Per-round aggregate queue pressure."""
    peak_iq: Dict[str, int] = field(default_factory=dict)
    peak_oq: Dict[str, int] = field(default_factory=dict)
    total_tasks: Dict[str, int] = field(default_factory=dict)

    def record(self, task: str, per_tile_in: np.ndarray,
               per_tile_out: np.ndarray):
        self.peak_iq[task] = max(self.peak_iq.get(task, 0),
                                 int(per_tile_in.max(initial=0)))
        self.peak_oq[task] = max(self.peak_oq.get(task, 0),
                                 int(per_tile_out.max(initial=0)))
        self.total_tasks[task] = self.total_tasks.get(task, 0) + \
            int(per_tile_in.sum())
