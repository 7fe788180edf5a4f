"""Device milliseconds of the cohort train step and scan programs per
simulated round, in the traced session."""

PROGRAMS = r"^jit_(step|train_scan)$"


def read(run):
    t, s = run.window.trace, run.window.traced
    if t is None or s is None or not s.rounds:
        return None
    ns = t.programs(PROGRAMS)
    return ns / t.devices / 1e6 / s.rounds if ns else None
