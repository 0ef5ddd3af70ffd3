"""The work a cell's algorithm needs, counted from shapes and counters.

These are the numerators of the benchmark's shares of a peak. They count
what the algorithm must do, not what an implementation happens to do, so a
faster implementation cannot push a share past 100 %.
"""
from __future__ import annotations

WORD = 4   # bytes of one f32 value or int32 vertex id


def moe_flops_per_token(d_model: int, n_experts: int, top_k: int,
                        d_expert: int) -> float:
    """Model FLOPs of one token through one expert layer: the router
    (2·D·E) and top_k SwiGLU experts of three D×F products each
    (top_k · 3 · 2·D·F). No capacity padding, no dropped-token savings."""
    return 2.0 * d_model * n_experts + top_k * 3 * 2.0 * d_model * d_expert


def graph_job_bytes(messages, n_vertices: int, msg_words: int,
                    state_words_read: int, state_words_written: int) -> float:
    """Least bytes one graph job must move through memory.

    ``messages`` holds the per-round message counts (``AppStats.messages``).
    Each message reads its destination id and its source's value, plus the
    edge weight where the app uses one: ``msg_words`` words. Each round
    reads and writes every vertex's state once: ``state_words_read`` and
    ``state_words_written`` words per vertex.
    """
    rounds = len(messages)
    per_round_state = n_vertices * (state_words_read + state_words_written)
    return WORD * (msg_words * float(sum(int(m) for m in messages))
                   + rounds * per_round_state)
