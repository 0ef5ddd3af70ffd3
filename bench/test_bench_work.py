"""Peaks and the work counts behind moe.mfu and graph.round_roofline."""
import pytest

from bench import peaks, work


def test_v5e_peaks_and_unknown_kind_raises():
    assert peaks.peak("TPU v5 lite", "bf16_flops") == 197e12
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        peaks.peak("cpu", "bf16_flops")


def test_moe_flops_per_token_at_olmoe_widths():
    # router 2*2048*64 + top-8 * 3 products * 2*2048*1024
    assert work.moe_flops_per_token(2048, 64, 8, 1024) == 100925440.0


@pytest.mark.parametrize("messages,n,words,want", [
    ([10, 20], 5, (2, 1, 1), 4 * (2 * 30 + 2 * 5 * 2)),
    ([7] * 20, 3, (2, 2, 1), 4 * (2 * 140 + 20 * 3 * 3)),
    ([], 100, (2, 1, 1), 0.0),
])
def test_graph_job_bytes(messages, n, words, want):
    assert work.graph_job_bytes(messages, n, *words) == want
