"""Share of the traced stretch in which no XLA operation ran on the
device, averaged over the devices used (profiler trace)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
