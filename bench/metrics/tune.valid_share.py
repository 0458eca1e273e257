"""Trials neither refused by the compiler nor wrong, over all trials."""


def read(rec):
    c = rec["counters"]
    trials = c.get("tune.trials")
    if not trials:
        return None
    return 100.0 * (trials - c["tune.refused"] - c["tune.wrong"]) / trials
