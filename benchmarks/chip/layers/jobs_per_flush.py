"""Training jobs per vmapped flush of the batched engine
(``engine.jobs_run / engine.flushes``, summed over the window's
sessions)."""


def read(run):
    w = run.window
    flushes = sum(s.flushes for s in w.sessions)
    return sum(s.jobs for s in w.sessions) / flushes if flushes else None
