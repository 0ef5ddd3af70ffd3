"""graph.round_roofline: the least bytes the window's jobs had to move
(``bench/work.py``: each message's destination id, source value and, where
used, edge weight; each vertex's state read and written once per round)
over what the chip's HBM moves at its peak in the device busy time inside
those jobs, in percent."""
from bench import peaks, work


def read(record, summary, device_kind):
    jobs = record.get("jobs")
    if summary is None or not jobs:
        return None
    busy = summary["span_busy_s"].get("bench.job", [])
    if len(busy) != len(jobs) or sum(busy) <= 0:
        return None
    w = record["work"]
    need = sum(work.graph_job_bytes(j["messages"], record["n_vertices"],
                                    w["msg_words"], w["state_words_read"],
                                    w["state_words_written"]) for j in jobs)
    return 100.0 * need / (peaks.peak(device_kind, "hbm_bytes_per_s")
                           * sum(busy))
