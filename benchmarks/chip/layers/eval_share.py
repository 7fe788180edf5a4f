"""Share of the traced window spent inside ``engine.evaluate_models``,
once the models to evaluate are on the device."""


def read(run):
    t = run.window.trace
    if t is None:
        return None
    return 100.0 * t.span_ns("bench.evaluate") / t.window_ns
