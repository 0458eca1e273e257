"""XLA compiles during the traced batch, from the program's compile
counter: every shape is warmed up in set-up, so 0 is expected."""

from bench.lib import program


def read(rec):
    s = program.summary()
    if s is None or "repro.serve.generate" not in s["spans"]:
        return None
    return s["counters"].get(program.COMPILES, 0)
