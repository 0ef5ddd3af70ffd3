"""moe.share_mfu: model FLOP utilisation of one chip's share of a stack of
expert layers: tokens per second of the window times the model FLOPs of
one token through every layer (``bench/work_share.py``, with the held
tasks per token the reference counted on the sampled steps) over the
chip's bf16 peak (``bench/peaks.py``), in percent."""
from bench import peaks, work_share


def read(record, summary, device_kind):
    if "held_tasks" not in record:
        return None
    s = record["shape"]
    flops = record["layers"] * work_share.share_flops_per_token(
        s["d_model"], s["n_experts"], s["d_expert"], s["d_shared"],
        record["held_tasks"] / record["tokens_per_step"])
    return (100.0 * record["tokens_per_s"] * flops
            / peaks.peak(device_kind, "bf16_flops"))
