"""Time per trial in which the runner times candidates (warm-up and
repeat calls), from the program's ``repro.runner.time`` spans over the
traced round."""

from bench.lib import program


def read(rec):
    return program.per_trial_ms(("repro.runner.time",))
