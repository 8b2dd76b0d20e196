"""Share of the HBM bandwidth peak that the search's levels reach in the
traced stretch: the bytes a level must move at its rung's shape
(``harness/levelbytes.py``), summed over every level each key or history
ran, over the device busy time summed over the devices used, over the
peak of the device kind (``harness/peaks.py``)."""

from harness.levelbytes import level_bytes
from harness.peaks import peak


def read(run):
    if run.trace is None:
        return None
    byts = sum(levels * level_bytes(cap, win, exp, crash)
               for ck in run.traced
               for cap, win, exp, crash, levels in ck.work)
    busy = sum(run.trace.busy_s.values())
    if byts <= 0 or busy <= 0:
        return None
    return 100.0 * byts / busy / peak(run.device_kind, "hbm_bytes_per_s")
