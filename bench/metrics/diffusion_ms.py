"""Device time of the `diffusion` op per simulated step (ms/step)."""


def read(trace, cfg, peaks):
    if not trace.has_scope("diffusion") or trace.steps == 0:
        return None
    return trace.scope_seconds()["diffusion"] / trace.steps * 1e3
