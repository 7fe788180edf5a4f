"""Share of the cohort step programs' (``jit_step``, ``jit_train_scan``)
device time in the traced session under the ``unpack`` or ``pack`` scopes:
the flat buffer's slicing into leaves and the gradients' packing back
(op scopes from the compiled text of the step programs the session ran)."""

import program_trace


def read(run):
    return program_trace.read("train_glue_share", run.window)
