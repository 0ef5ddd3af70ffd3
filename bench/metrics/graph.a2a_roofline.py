"""graph.a2a_roofline: the least bytes the window's jobs had to move
between chips (``bench/work_fabric.py``: each cross-owner message's
``msg_words`` words) over what the chips' interconnect moves at its peak
(each chip's ICI peak, as egress, times the chips) in the device self time
under ``dcra.route.a2a`` per chip, in percent."""
from bench import work_fabric


def read(record, summary, device_kind):
    a2a_s = record.get("scope_s", {}).get("dcra.route.a2a")
    jobs = record.get("jobs", [])
    if not a2a_s or not jobs or "n_dev" not in record or any(
            "cross_messages" not in j for j in jobs):
        return None
    need = sum(work_fabric.cross_bytes(j["cross_messages"],
                                       record["work"]["msg_words"])
               for j in jobs)
    return 100.0 * need / (record["n_dev"] * work_fabric.ici_peak(device_kind)
                           * a2a_s)
