"""Host milliseconds per ``engine.aggregate`` call in the traced session,
from the moment its input models are on the device to the moment its
mean is: stacking, dispatch, pad and relayout glue and the kernel. (The
traced session waits for both ends, so the span holds no train-step work
still queued before the call.)"""


def read(run):
    t = run.window.trace
    if t is None:
        return None
    d = t.span_durations("bench.aggregate")
    return sum(d) / len(d) / 1e6 if d else None
