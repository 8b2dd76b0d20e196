"""90th percentile of the time from a check's start to its verdict, over
every check in the window (exclusive method of ``statistics.quantiles``;
None with fewer than 10 checks)."""

import statistics


def read(run):
    walls = [ck.wall_s for ck in run.window]
    if len(walls) < 10:
        return None
    return statistics.quantiles(walls, n=10)[8]
