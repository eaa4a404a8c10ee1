"""Host ms per scheduling tick in the engine's own expire, preempt, admit
and evict spans (the round launch is not counted)."""

PHASES = ("expire", "preempt", "admit", "evict")


def read(rec):
    spans = rec.get("spans")
    if not spans:
        return None
    ticks = sum(1 for s in spans if s[0] == "tick")
    if ticks == 0:
        return None
    return sum(s[3] for s in spans if s[0] in PHASES) * 1e-6 / ticks
