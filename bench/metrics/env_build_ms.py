"""Device time of the `env_build` op per simulated step (ms/step)."""


def read(trace, cfg, peaks):
    if not trace.has_scope("env_build") or trace.steps == 0:
        return None
    return trace.scope_seconds()["env_build"] / trace.steps * 1e3
