"""graph.launch_s: host seconds in ``launch_program`` per job (edge
packing, state packing, upload and dispatch), the mean over the window's
jobs, on the benchmark's host clock."""


def read(record, summary, device_kind):
    jobs = record.get("jobs")
    if not jobs:
        return None
    return sum(j["launch_s"] for j in jobs) / len(jobs)
