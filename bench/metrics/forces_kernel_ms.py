"""Device self time of the force pass's kernel stage (the program's
`cell_kernel` scope) per simulated step (ms/step)."""

import stages


def read(trace, cfg, peaks):
    return stages.run_readings(trace)[0]["cell_kernel"]
