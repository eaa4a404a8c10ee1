"""Host ms per admitted request: the continuous engine's ``admit`` spans
over the ``admitted`` argument they carry (ticks that admit nothing count
in the time)."""


def read(rec):
    spans = [s for s in rec.get("spans") or ()
             if s[0] == "admit" and s[4] and "admitted" in s[4]]
    admitted = sum(s[4]["admitted"] for s in spans)
    if not admitted:
        return None
    return sum(s[3] for s in spans) * 1e-6 / admitted
