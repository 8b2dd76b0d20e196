"""Device busy time of the traced stretch (mean over the devices) over
the levels a chip's own loop ran (the system's
``jtpu_keyed_chip_levels_total`` over the cell's chips), in
microseconds. None where the system keeps no per-chip counter or the
run has no device trace."""


def read(run):
    levels = run.trace_counters.get("chip-levels", 0) / run.chips
    if run.trace is None or levels <= 0:
        return None
    return 1e6 * run.trace.mean_busy_s / levels
