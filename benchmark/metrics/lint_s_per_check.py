"""History-gate seconds per window check: the system's
``checker.lint`` spans (the pre-search lint of every history or key),
summed over the window's checks and divided by their number. None
unless every window check holds exactly one ``checker.search`` span."""

from harness import checkspans


def read(run):
    return checkspans.seconds_per_check(run, "checker.lint")
