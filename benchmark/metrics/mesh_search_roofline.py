"""The per-chip search kernel's share of its roofline on the mesh,
counted as ``search_roofline`` counts the one-chip kernel's: the bytes
a level must move at its rung's shape (``harness/levelbytes.py``),
summed over every level each key ran, over the device busy time summed
over the devices used, over the HBM peak of the device kind
(``harness/peaks.py``)."""

from harness import spec


def read(run):
    return spec.load_module("metrics", "search_roofline").read(run)
