"""Share of the traced window in the program's event-handler spans
(``repro.sim.event``) but outside its engine calls (``repro.engine.result``,
``.aggregate``, ``.evaluate``): the event loop, the protocol and the
network model."""

import program_trace


def read(run):
    return program_trace.read("loop_self_share", run.window)
