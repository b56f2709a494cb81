"""Device time of the `sort` op per firing (ms); it fires every
``sort_frequency`` steps."""


def read(trace, cfg, peaks):
    fired = trace.firings("sort")
    if not trace.has_scope("sort") or fired == 0:
        return None
    return trace.scope_seconds()["sort"] / fired * 1e3
