"""Output tokens observed inside the window over the window's seconds.
A token counts when the load generator observes it after a step, so
long requests that straddle the window's ends still count their share."""


def read(run):
    w = run.window
    return w.tokens / (w.t1 - w.t0)
