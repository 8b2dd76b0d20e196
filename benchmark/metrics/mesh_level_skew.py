"""The level skew between chips in the traced stretch: the levels the
launches held the chips for (the system's ``jtpu_search_levels_total``,
each launch's slowest chip, times the chips) over the levels the chips'
own loops ran (``jtpu_keyed_chip_levels_total``, each chip's slowest
key, summed). 1.0 means every chip ran as many levels as the slowest;
None where the system keeps no per-chip counter."""


def read(run):
    chip = run.trace_counters.get("chip-levels", 0)
    if chip <= 0:
        return None
    return run.chips * run.trace_counters["levels"] / chip
