"""The model FLOPs of one chip's share of an expert layer, counted from
shapes and the reference's routing.

The numerator of ``moe.share_mfu``. Like ``bench/work.py`` it counts what
the layer must do, not what an implementation does: no capacity padding,
no dropped task, and only the experts this chip holds.
"""
from __future__ import annotations


def share_flops_per_token(d_model: int, n_experts: int, d_expert: int,
                          d_shared: int, held_tasks_per_token: float) -> float:
    """Model FLOPs of one token through one layer on the chip: the router
    over all ``n_experts`` (2·D·E), the shared expert's three D×Fs products
    (3 · 2·D·Fs), and three D×F products for each of the token's routed
    tasks whose expert is held here (``held_tasks_per_token`` on average,
    counted from the reference's routing)."""
    return (2.0 * d_model * n_experts + 3 * 2.0 * d_model * d_shared
            + held_tasks_per_token * 3 * 2.0 * d_model * d_expert)
