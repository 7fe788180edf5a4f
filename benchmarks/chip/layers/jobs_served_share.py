"""Share of the jobs trained in the traced session's flushes that a
``result()`` call took (the engine's ``jobs_served`` over ``jobs_run``):
the rest trained for nodes that never claimed them."""

import program_trace


def read(run):
    return program_trace.read("jobs_served_share", run.window)
