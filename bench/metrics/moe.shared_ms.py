"""moe.shared_ms: device self time under the program's ``dcra.moe.shared``
scope in the traced window (``bench/scopes.py``, read by the driver into
``record["scope_s"]``) per layer and step, in milliseconds."""


def read(record, summary, device_kind):
    scope_s = record.get("scope_s", {}).get("dcra.moe.shared")
    if scope_s is None or not record.get("steps"):
        return None
    return 1e3 * scope_s / (record["steps"] * record["layers"])
