"""graph.wire_fill: the messages the window's jobs routed
(``AppStats.messages``) over the wire slots their rounds shipped: rounds
times chips times the slots each chip sends per round through the
all-to-all (the program's ``wire_plan``, read by the driver into
``record["wire"]``), in percent."""


def read(record, summary, device_kind):
    wire, jobs = record.get("wire"), record.get("jobs")
    if not wire or not jobs:
        return None
    slots = sum(j["rounds"] for j in jobs) * wire["n_dev"] * sum(
        wire["slots_per_round"])
    if slots == 0:
        return None
    return 100.0 * sum(sum(j["messages"]) for j in jobs) / slots
