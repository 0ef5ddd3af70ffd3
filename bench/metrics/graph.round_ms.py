"""graph.round_ms: device busy time inside the window's jobs (the union of
device operations within each ``bench.job`` span) over the rounds those
jobs ran (``AppStats.rounds``), in milliseconds."""


def read(record, summary, device_kind):
    jobs = record.get("jobs")
    if summary is None or not jobs:
        return None
    busy = summary["span_busy_s"].get("bench.job", [])
    rounds = sum(j["rounds"] for j in jobs)
    if len(busy) != len(jobs) or rounds == 0 or sum(busy) <= 0:
        return None
    return 1e3 * sum(busy) / rounds
