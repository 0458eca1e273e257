"""XLA compiles per trial in the traced round (programs loaded from the
persistent cache are not compiles), from the program's compile counter."""

from bench.lib import program


def read(rec):
    s = program.summary()
    if s is None or not s["counters"].get(program.TRIALS):
        return None
    compiles = s["counters"].get(program.COMPILES)
    if compiles is None:
        return None
    return compiles / s["counters"][program.TRIALS]
