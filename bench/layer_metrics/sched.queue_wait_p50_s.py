"""Median time a request waited for a slot: the Engine's queue wait
(``Completion.queue_wait_s``, submit to admission) plus the lateness
of its send, over every request sent in the window that was admitted."""

from bench.harness.stats import nearest_rank


def read(run):
    return nearest_rank([s.queue_wait_s + (s.t_send - s.t_due)
                         for s in run.sent if s.queue_wait_s is not None], 50)
