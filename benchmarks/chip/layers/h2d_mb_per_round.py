"""MB (1e6 bytes) the engine copied to the device for training inputs
(its ``batch_bytes_h2d``: indices, masks, flags and any shard uploaded) per
round of the traced session."""

import program_trace


def read(run):
    return program_trace.read("h2d_mb_per_round", run.window)
