"""Seconds from the first line of ``run.py`` to the window's start:
imports, device start, the pool of histories, and one check of every
pool item, which compiles or loads from the cache every shape used."""


def read(run):
    return run.setup_s
