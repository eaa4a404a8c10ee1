"""Host ms per launch in the engine's ``stage`` spans: building a launch's
event block and handing it to the device."""


def read(rec):
    durs = [s[3] for s in rec.get("spans") or () if s[0] == "stage"]
    if not durs:
        return None
    return sum(durs) * 1e-6 / len(durs)
