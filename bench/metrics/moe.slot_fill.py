"""moe.slot_fill: the held experts' tasks of one layer and step (counted by
the reference on the sampled steps) over the expert rows the program
allocates for them (``slot_plan(...).expert_slots``), in percent."""


def read(record, summary, device_kind):
    if "held_tasks" not in record or not record.get("expert_slots"):
        return None
    return 100.0 * record["held_tasks"] / record["expert_slots"]
