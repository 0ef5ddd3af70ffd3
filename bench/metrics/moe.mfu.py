"""moe.mfu: model FLOP utilisation of the expert layer: tokens per second
of the traced window times the model FLOPs of one token
(``bench/work.py``) over the chip's bf16 peak (``bench/peaks.py``), in
percent."""
from bench import peaks, work


def read(record, summary, device_kind):
    if "tokens_per_s" not in record:
        return None
    s = record["shape"]
    flops = work.moe_flops_per_token(s["d_model"], s["n_experts"], s["top_k"],
                                     s["d_expert"])
    return (100.0 * record["tokens_per_s"] * flops
            / peaks.peak(device_kind, "bf16_flops"))
