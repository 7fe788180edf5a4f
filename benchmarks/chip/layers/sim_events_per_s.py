"""Simulator events processed per wall second over the whole window
(``Simulator.events_processed``, summed over the window's sessions)."""


def read(run):
    w = run.window
    events = sum(s.events for s in w.sessions)
    return events / w.seconds if events else None
