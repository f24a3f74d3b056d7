"""Load generators on the wall clock: an open loop of due times and a
closed loop of clients.  Both hand the harness indices into one
pre-generated stream of transactions, so nothing is generated inside the
measured window."""
from __future__ import annotations

from collections import deque
from typing import List, Optional, Tuple

import numpy as np


class OpenLoop:
    """Requests fall due at fixed times (seconds from the loop's start),
    whether or not earlier ones have been answered.  A request is sent no
    earlier than it falls due; it may be sent later, and its latency is
    taken from its due time all the same."""

    def __init__(self, due: np.ndarray):
        self.due = np.asarray(due, np.float64)
        self.next = 0

    def take(self, elapsed: float, limit: Optional[int] = None) -> range:
        """Indices of the requests due by ``elapsed`` and not yet sent, the
        earliest ``limit`` of them where ``limit`` is given; the rest stay
        due and are taken first next time."""
        i0 = self.next
        n = int(np.searchsorted(self.due, elapsed, side="right"))
        if limit is not None:
            n = min(n, i0 + max(0, int(limit)))
        self.next = max(i0, n)
        return range(i0, self.next)

    def due_before(self, elapsed: float) -> int:
        """How many requests fall due before ``elapsed``."""
        return int(np.searchsorted(self.due, elapsed, side="left"))

    def until_next(self, elapsed: float) -> float:
        """Seconds until the next request falls due (inf when none is
        left)."""
        if self.next >= len(self.due):
            return float("inf")
        return max(0.0, float(self.due[self.next]) - elapsed)


class ClientPool:
    """``n_clients`` closed-loop clients with zero think time: each holds
    at most one outstanding request and sends its next one, the next
    transaction of the stream, as soon as the last is answered."""

    def __init__(self, n_clients: int, stream_len: int):
        if n_clients < 1 or stream_len < 1:
            raise ValueError(f"need clients and a stream, got "
                             f"{n_clients} and {stream_len}")
        self.idle: deque = deque(range(n_clients))
        self.outstanding: List[Tuple[int, object]] = []
        self.stream_len = stream_len
        self.next = 0          # stream position; wraps past the end

    def take(self, limit: int) -> List[Tuple[int, int]]:
        """Up to ``limit`` idle clients, each with the stream index of the
        transaction it sends now."""
        out = []
        while self.idle and len(out) < limit:
            out.append((self.idle.popleft(), self.next % self.stream_len))
            self.next += 1
        return out

    def sent(self, client: int, handle) -> None:
        """Record the handle of the request ``client`` just sent."""
        self.outstanding.append((client, handle))

    def reap(self, done) -> int:
        """Return every client whose request ``done(handle)`` says is
        answered to the idle queue; returns how many."""
        keep, n = [], 0
        for c, h in self.outstanding:
            if done(h):
                self.idle.append(c)
                n += 1
            else:
                keep.append((c, h))
        self.outstanding = keep
        return n
