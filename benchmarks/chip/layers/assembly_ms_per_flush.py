"""Host milliseconds in the program's ``repro.engine.assemble`` spans (a
flush's index build and each group's fill of its indices, masks and
flags) per vmapped group, in the traced session."""

import program_trace


def read(run):
    return program_trace.read("assembly_ms_per_flush", run.window)
