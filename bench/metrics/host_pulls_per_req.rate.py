"""Device-to-host reads per returned request: the ``pulls`` argument of the
engine's ``admit``, ``evict`` and ``legacy_batch`` spans over the
``requests`` argument of its ``evict`` and ``legacy_batch`` spans."""

PULLS = ("admit", "evict", "legacy_batch")
REQUESTS = ("evict", "legacy_batch")


def read(rec):
    spans = [s for s in rec.get("spans") or () if s[4]]
    requests = sum(s[4]["requests"] for s in spans
                   if s[0] in REQUESTS and "requests" in s[4])
    if not requests:
        return None
    return sum(s[4]["pulls"] for s in spans
               if s[0] in PULLS and "pulls" in s[4]) / requests
