"""Device time of the `forces` op per simulated step (ms/step)."""


def read(trace, cfg, peaks):
    if not trace.has_scope("forces") or trace.steps == 0:
        return None
    return trace.scope_seconds()["forces"] / trace.steps * 1e3
