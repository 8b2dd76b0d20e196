"""Host seconds per check in the window: wall time less the system's
host-clock device-call time (``compile-s`` + ``execute-s``), summed over
the window's checks and divided by their number. Packing, planning,
escalation decisions and supervision between device calls."""


def read(run):
    host = sum(ck.wall_s - ck.counters["compile-s"] - ck.counters["execute-s"]
               for ck in run.window)
    return host / len(run.window)
