"""Occupied slots per launch over the engine's slots (%): the ``active``
argument of the continuous engine's ``round`` spans, or the ``batch``
argument of the drain path's ``legacy_batch`` spans."""


def read(rec):
    used = [s[4]["active"] for s in rec.get("spans") or ()
            if s[0] == "round" and s[4]]
    used += [s[4]["batch"] for s in rec.get("spans") or ()
             if s[0] == "legacy_batch" and s[4]]
    if not used:
        return None
    return 100.0 * sum(used) / (len(used) * rec["slots"])
