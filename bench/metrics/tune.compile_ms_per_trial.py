"""Time per trial in which the runner builds and compiles candidates
(refusals included), from the program's ``repro.runner.compile`` spans
over the traced round."""

from bench.lib import program


def read(rec):
    return program.per_trial_ms(("repro.runner.compile",))
