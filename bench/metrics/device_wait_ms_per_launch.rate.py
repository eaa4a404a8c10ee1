"""Host ms blocked on the device per wait: the engine's ``wait`` spans, one
before each readout (every drain-path batch; a continuous tick only when a
request finishes)."""


def read(rec):
    durs = [s[3] for s in rec.get("spans") or () if s[0] == "wait"]
    if not durs:
        return None
    return sum(durs) * 1e-6 / len(durs)
