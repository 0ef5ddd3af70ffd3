"""moe.device_idle: the share of the traced window in which no operation
ran on the device, in percent (``bench/trace.py``)."""


def read(record, summary, device_kind):
    if summary is None or "tokens_per_s" not in record:
        return None
    return 100.0 * summary["idle_share"]
