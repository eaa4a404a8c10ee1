"""Host ms per submitted request in the engine's ``enqueue`` spans: event
validation, the density count and queueing."""


def read(rec):
    durs = [s[3] for s in rec.get("spans") or () if s[0] == "enqueue"]
    if not durs:
        return None
    return sum(durs) * 1e-6 / len(durs)
