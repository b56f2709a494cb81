"""Device self time of the force pass's scatter stage (the program's
`cell_scatter` scope) per simulated step (ms/step)."""

import stages


def read(trace, cfg, peaks):
    return stages.run_readings(trace)[0]["cell_scatter"]
