"""The decode step's share of the chip's peak: its operations over the
bf16 peak, over the decode program's device time in the traced batch."""

from bench.lib.costs import share


def read(rec):
    d = rec["trace"].get("decode")
    if not d or not d["steps"]:
        return None
    return share(d["compute_s"], d["device_s"])
