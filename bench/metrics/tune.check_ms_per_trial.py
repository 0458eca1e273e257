"""Time per trial in which the runner checks candidates: placing the
inputs, compiling and running the reference, and the first call with its
error, from the program's spans over the traced round."""

from bench.lib import program

NAMES = ("repro.runner.inputs", "repro.runner.reference",
         "repro.runner.check")


def read(rec):
    return program.per_trial_ms(NAMES)
