"""Host time per trial outside the program's runner: proposing, evolving,
the cost model and the session's bookkeeping (the tuner layer)."""


def read(rec):
    trials = rec["counters"].get("tune.trials")
    runner_s = rec["spans"].get("bench.runner")
    if not trials or runner_s is None:
        return None
    return 1e3 * (rec["window_s"] - runner_s) / trials
