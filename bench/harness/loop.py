"""The measured window's common parts: a load loop drives ``Engine.submit``
and ``Engine.step`` and observes every token as it appears.  Each kind
of loop is a file of its own under ``bench/loops/``, found by the mix's
``"loop"``.

Times are the host's monotonic clock.  A token counts when the loop
observes it after a step, not when its request completes.  Each send
records when it was due and when it went; in a closed loop a client
sends at once when it sees its previous request end, and the gap
between the two is the send's lateness.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench.harness import adapter
from repro.runtime.engine import ST_OK, Request

clock = time.perf_counter
FOLLOW_LIMIT_S = 150.0      # how long requests are followed after the window


@dataclasses.dataclass
class Sent:
    uid: int
    prompt: np.ndarray
    max_new: int
    t_due: float
    t_send: float
    n: int = 0
    status: str | None = None
    queue_wait_s: float | None = None
    tokens: np.ndarray | None = None

    @property
    def ok(self) -> bool:
        return self.status == ST_OK


@dataclasses.dataclass
class Window:
    t0: float
    t1: float = 0.0
    tokens: int = 0              # tokens observed inside the window


class Loop:
    """What every load loop shares: sending a request, observing the
    tokens of those in flight, and following them to their end.  A loop
    kind ``<k>`` is ``bench/loops/<k>.py`` with ``make(eng, traffic,
    seconds, follow)`` returning a subclass whose ``run(on_tick)``
    drives the window and returns its :class:`Window`; ``follow`` says
    whether requests still running at the window's close are followed
    to their end, with no new sends."""

    def __init__(self, eng, traffic, seconds: float, follow: bool):
        self.eng = eng
        self.traffic = traffic
        self.seconds = seconds
        self.follow = follow
        self.sent: list[Sent] = []
        self.live: dict[int, Sent] = {}

    def _send(self, t_due: float) -> None:
        req = self.traffic.next()
        uid = req.index
        t = clock()
        self.eng.submit(Request(uid, req.prompt,
                                max_new_tokens=req.max_new_tokens))
        s = Sent(uid, req.prompt, req.max_new_tokens, t_due, t)
        self.sent.append(s)
        self.live[uid] = s

    def _observe(self) -> tuple[int, list[Sent]]:
        """Count new tokens and take ended requests; returns how many
        tokens are new and which requests ended."""
        new = 0
        for uid, s in list(self.live.items()):
            n = adapter.progress(self.eng, uid)
            if n > s.n:
                new += n - s.n
                s.n = n
        ended = []
        for c in self.eng.collect():
            s = self.live.pop(c.uid)
            s.status, s.tokens = c.status, c.tokens
            s.queue_wait_s = c.queue_wait_s
            ended.append(s)
        return new, ended

    def follow_to_end(self) -> None:
        if not self.follow:
            return
        limit = clock() + FOLLOW_LIMIT_S
        while self.live and clock() < limit:
            self.eng.step()
            self._observe()
