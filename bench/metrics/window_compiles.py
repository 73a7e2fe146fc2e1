"""Scheduler: executables the client compiled inside the window (its
compile counter at the close less at the start).  Warm-up leaves 0."""


def read(run):
    return float(run.compiles)
