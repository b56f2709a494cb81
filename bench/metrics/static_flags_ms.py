"""Device time of the `static_flags` op per simulated step (ms/step)."""


def read(trace, cfg, peaks):
    if not trace.has_scope("static_flags") or trace.steps == 0:
        return None
    return trace.scope_seconds()["static_flags"] / trace.steps * 1e3
