"""Share of the device's idle time in the traced window that falls inside
the program's ``repro.engine.assemble`` spans."""

import program_trace


def read(run):
    return program_trace.read("idle_in_assembly_share", run.window)
