"""Supervision seconds per window check: the time of each check's
``checker.search`` span that no ``checker.lint``, ``checker.pack``,
``checker.plan``, ``checker.segment`` or ``checker.device.*`` span
covers (readback, result assembly, bookkeeping between segments),
summed over the window's checks and divided by their number. None
unless every window check holds exactly one ``checker.search`` span."""

from harness import checkspans


def read(run):
    return checkspans.root_self_seconds_per_check(run)
