"""Executable shapes called for the first time inside the window (the
system's ``compile_delta`` ``cold``); every one is a compile or a cache
load in the measured time. Should read 0."""


def read(run):
    return run.window_counters["cold"]
