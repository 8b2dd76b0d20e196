"""Device busy time of the traced stretch (mean over devices) over the
search levels its device calls advanced (the system's
``jtpu_search_levels_total``; a keyed batch call counts the levels its
loop ran), in microseconds."""


def read(run):
    levels = run.trace_counters.get("levels", 0)
    if run.trace is None or levels <= 0:
        return None
    return 1e6 * run.trace.mean_busy_s / levels
