"""Device executable calls per check in the window: segments summed
over rungs for a single history, batch calls for keyed runs (the
system's ``cold`` + ``cache-hits`` counters)."""


def read(run):
    c = run.window_counters
    return (c["cold"] + c["cache-hits"]) / len(run.window)
