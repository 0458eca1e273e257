"""The whole network pass's share of the chip's peak: the operators'
operations over the peak of their dtype, over the pass program's device
time in the traced passes."""

from bench.lib.costs import share


def read(rec):
    net = rec["trace"].get("net")
    if not net:
        return None
    return share(net["passes"] * sum(net["compute_s"].values()),
                 net["pass_device_s"])
