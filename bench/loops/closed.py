"""Closed loop: ``clients`` callers, each sending its next request as
soon as it sees its last one end, for ``seconds``.  The window opens with
every client sending at once."""

from __future__ import annotations

import jax

from bench.harness.loop import Loop, Window, clock


class ClosedLoop(Loop):

    def run(self, on_tick=None) -> Window:
        """``on_tick(now, window)`` runs after each step of the window
        (the traced run starts and stops its trace there)."""
        w = Window(t0=clock())
        t_end = w.t0 + self.seconds
        with jax.profiler.TraceAnnotation("bench.loadgen"):
            for _ in range(self.traffic.clients):
                self._send(w.t0)
        while True:
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                self.eng.step()
            t = clock()
            with jax.profiler.TraceAnnotation("bench.results"):
                new, ended = self._observe()
            w.tokens += new
            w.t1 = t
            if t >= t_end:
                break
            if on_tick is not None:
                on_tick(t, w)
            with jax.profiler.TraceAnnotation("bench.loadgen"):
                for _ in ended:
                    self._send(t)
        return w


def make(eng, traffic, seconds: float, follow: bool) -> ClosedLoop:
    return ClosedLoop(eng, traffic, seconds, follow)
