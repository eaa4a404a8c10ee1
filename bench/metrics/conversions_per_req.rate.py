"""IMA conversions per returned request: the ``conversions`` argument of
the engine's ``round`` spans (active slot-steps times the columns the
kernel converts) over the ``requests`` argument of its ``evict`` spans.  A
request of T steps on C columns needs T C; more means work that retires
nothing."""


def read(rec):
    spans = [s for s in rec.get("spans") or () if s[4]]
    requests = sum(s[4]["requests"] for s in spans
                   if s[0] == "evict" and "requests" in s[4])
    conversions = sum(s[4]["conversions"] for s in spans
                      if s[0] == "round" and "conversions" in s[4])
    if not requests or not conversions:
        return None
    return conversions / requests
