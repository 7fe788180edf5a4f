"""The aggregation program's share of its roofline in the traced session:
the HBM bytes the algorithm needs for the calls made, at the unpadded
width N, over HBM bandwidth, against the device time of the program
``jit__fused`` (its ``XLA Modules`` events). The Pallas kernel's own
events are not the time to take: on the TPU the compiler places the
padded ``(P, rows, 128)`` stack in VMEM, so the pad fusion before the
kernel reads the models from HBM and the slice after it writes the mean
back, while the kernel itself touches no HBM."""

from counting import aggregation_bytes

PROGRAM = r"^jit__fused$"


def read(run):
    t, s = run.window.trace, run.window.traced
    if t is None or s is None or not s.agg_sizes:
        return None
    program_ns = t.programs(PROGRAM) / t.devices
    if not program_ns:
        return None
    need = sum(aggregation_bytes(run.n_params, p) for p in s.agg_sizes)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / (program_ns / 1e9)
