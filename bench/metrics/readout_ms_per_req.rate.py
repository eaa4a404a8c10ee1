"""Host ms per returned request in the engine's ``readout`` spans: the
summed readout time over the ``requests`` argument of the ``evict`` and
``legacy_batch`` spans that hold them."""

PARENTS = ("evict", "legacy_batch")


def read(rec):
    spans = rec.get("spans") or ()
    requests = sum(s[4]["requests"] for s in spans
                   if s[0] in PARENTS and s[4] and "requests" in s[4])
    if not requests:
        return None
    return sum(s[3] for s in spans if s[0] == "readout") * 1e-6 / requests
