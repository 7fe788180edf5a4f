"""Training FLOPs of the real (unpadded) samples delivered in the window,
from the config's count per sample, over the window's wall time times the
chip's bf16 peak."""


def read(run):
    w = run.window
    if not w.samples_trained:
        return None
    flops = w.samples_trained * run.flops_per_sample
    return 100.0 * flops / (w.seconds * run.peaks["bf16_flops_per_s"])
