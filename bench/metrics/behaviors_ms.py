"""Device time of the `behaviors` op per simulated step (ms/step)."""


def read(trace, cfg, peaks):
    if not trace.has_scope("behaviors") or trace.steps == 0:
        return None
    return trace.scope_seconds()["behaviors"] / trace.steps * 1e3
