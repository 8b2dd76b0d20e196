"""Packing seconds per window check: the system's ``checker.pack``
spans (the packed encoding of each history or key and its split into
device columns), summed over the window's checks and divided by their
number. None unless every window check holds exactly one
``checker.search`` span."""

from harness import checkspans


def read(run):
    return checkspans.seconds_per_check(run, "checker.pack")
