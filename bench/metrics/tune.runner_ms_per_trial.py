"""Time per trial inside the program's runner (compile, check against the
program's reference, time), from the benchmark's span around each call."""


def read(rec):
    trials = rec["counters"].get("tune.trials")
    runner_s = rec["spans"].get("bench.runner")
    if not trials or runner_s is None:
        return None
    return 1e3 * runner_s / trials
