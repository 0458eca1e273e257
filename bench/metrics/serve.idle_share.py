"""Share of the traced batch (prefill and decode steps) in which no
operation ran on the device."""


def read(rec):
    w = rec["trace"].get("window")
    if not w or not w["window_s"] > 0:
        return None
    return 100.0 * (1.0 - w["busy_s"] / w["window_s"])
