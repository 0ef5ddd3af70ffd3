"""The cross-chip work of a graph job on a fabric of several chips, and
the chips' interconnect peak.

Vertex ``v`` lives on chip ``v mod n_dev`` (the program's cyclic owner
layout). A BFS job sends one message along every out-edge of every vertex
it reaches, in the round after the vertex's level is reached; the message
must cross chips where the edge's two ends have different owners. Each
such message carries ``msg_words`` words (its destination and its value),
so ``cross_messages * msg_words * WORD`` is the least a job can move
between chips, whatever the wire pads. Counted from the oracle's hop
counts and the graph alone, it cannot grow with a faster implementation,
so a share of the interconnect built on it cannot pass 100 %.

Interconnect peak (ICI), keyed by ``device_kind``. Source: Google Cloud
documentation, "TPU v5e" (system architecture page): 1,600 Gbps of
inter-chip interconnect bandwidth per chip, 200e9 bytes/s. The benchmark
counts it as each chip's egress; where the links carry that much each way,
a share read against it only reads low, never past 100 %. A device kind
that is not in the table is an error.
"""
from __future__ import annotations

import numpy as np

from .work import WORD

ICI_PEAKS = {
    "TPU v5 lite": {"ici_bytes_per_s": 1600e9 / 8,
                    "source": "Google Cloud documentation, TPU v5e: "
                              "1,600 Gbps inter-chip interconnect per chip"},
}


def ici_peak(device_kind: str) -> float:
    """Interconnect bytes per second of one chip of ``device_kind``."""
    if device_kind not in ICI_PEAKS:
        raise KeyError(f"no interconnect peak recorded for device kind "
                       f"{device_kind!r}; known: {sorted(ICI_PEAKS)}")
    return ICI_PEAKS[device_kind]["ici_bytes_per_s"]


def cross_degrees(g, n_dev: int) -> np.ndarray:
    """Per vertex, its out-edges whose target has another owner."""
    rows = g.rows()
    cross = (rows % n_dev) != (g.col_idx.astype(np.int64) % n_dev)
    return np.bincount(rows[cross], minlength=g.n).astype(np.int64)


def cross_messages(cross_deg: np.ndarray, dist: np.ndarray) -> int:
    """Messages of one BFS job that cross chips: the cross-owner out-edges
    of every vertex the oracle reached (``dist >= 0``)."""
    return int(cross_deg[np.asarray(dist) >= 0].sum())


def cross_bytes(cross_msgs: int, msg_words: int) -> float:
    """Least bytes a job moves between chips."""
    return float(WORD * msg_words * int(cross_msgs))
