"""graph.sync_ms: device self time under the program's ``dcra.graph.sync``
scope (the round's scalar collectives: message, drop and convergence
counts) in the traced window, per chip (``bench/scopes.py``, read by the
driver into ``record["scope_s"]``), over the rounds of the window's jobs
(``AppStats.rounds``), in milliseconds."""


def read(record, summary, device_kind):
    scope_s = record.get("scope_s", {}).get("dcra.graph.sync")
    rounds = sum(j["rounds"] for j in record.get("jobs", []))
    if scope_s is None or rounds == 0:
        return None
    return 1e3 * scope_s / rounds
