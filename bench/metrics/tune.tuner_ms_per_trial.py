"""Host time per trial in the tuner layer, from the program's spans over
the traced round: the self time of the session, of proposing, of
reconciling and of the measure call around the runner. The session's
baselines only wait on the measuring thread and are left out."""

from bench.lib import program

NAMES = ("repro.session.tune_model", "repro.tuner.propose",
         "repro.tuner.reconcile", "repro.tuner.measure")


def read(rec):
    return program.per_trial_ms(NAMES)
