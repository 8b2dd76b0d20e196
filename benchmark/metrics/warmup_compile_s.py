"""Seconds of the system's cold first calls during set-up (its
``compile_delta`` ``compile-s``: compilation, or a load from the
persistent cache, plus one execution per executable shape)."""


def read(run):
    return run.warm["compile-s"]
