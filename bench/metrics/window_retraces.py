"""Traces of the program's schedule inside the measured window (count): its
`trace_schedule` host spans there.  Each is a compile the window paid for."""

import stages


def read(trace, cfg, peaks):
    return stages.run_readings(trace)[1]
