"""Share of the traced window in which the host was outside the engine's
public calls (``result``, ``aggregate``, ``evaluate_models``): the event
loop, the protocol and the network model (the benchmark's host spans)."""


def read(run):
    t = run.window.trace
    if t is None:
        return None
    inside = t.span_ns("bench.result", "bench.aggregate", "bench.evaluate")
    return 100.0 * (1.0 - inside / t.window_ns)
