"""Device self time of the force pass's gather stage (the program's
`cell_gather` scope) per simulated step (ms/step)."""

import stages


def read(trace, cfg, peaks):
    return stages.run_readings(trace)[0]["cell_gather"]
