"""Closed-loop arrivals: ``clients`` callers, each sending its next request
as soon as its previous one returns.  Nothing falls due on its own, so
the offered load is whatever the server sustains: a standing backlog.

An arrival kind gives the serving driver one class, ``Arrivals(mix,
seconds, seed)``, with ``start(t0)`` (the window opens), ``take(now)``
(the due times of the requests due by ``now`` and not yet taken),
``returned(times)`` (requests returned at these times) and
``next_due()`` (when the next request falls due on its own, or None).
Times are ``time.perf_counter`` seconds.
"""

from __future__ import annotations


class Arrivals:
    def __init__(self, mix: dict, seconds: float, seed: int):
        self._due = [None] * int(mix["clients"])   # None: due at once

    def start(self, t0: float) -> None:
        pass

    def take(self, now: float) -> list:
        due, self._due = self._due, []
        return [now if d is None else d for d in due]

    def returned(self, times) -> None:
        self._due.extend(times)

    def next_due(self):
        return None
