"""Host milliseconds in the program's ``repro.engine.dispatch`` spans (a
group's shard lookups, parameter stack, optimizer init, copies, staging
program and step dispatches) per vmapped group, in the traced session."""

import program_trace


def read(run):
    return program_trace.read("dispatch_ms_per_flush", run.window)
