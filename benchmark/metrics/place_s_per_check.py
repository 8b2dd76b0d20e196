"""Mesh placement seconds per window check: the system's
``checker.place`` spans (a cohort's layout over the mesh axis, padded
rows included, and its placement split over the chips), summed over
the window's checks and divided by their number. None unless every
window check holds exactly one ``checker.search`` span and the window
holds a placement span."""

from harness import checkspans

NAME = "checker.place"


def read(run):
    checks = checkspans.window_checks(run)
    if checks is None:
        return None
    ns = [e - s for c in checks for s, e, n in c if n == NAME]
    if not ns:
        return None
    return sum(ns) / 1e9 / len(checks)
