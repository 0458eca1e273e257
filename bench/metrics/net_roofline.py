"""The tuned network's kernels against their roofline: the sum over
operators of their least time (the larger of operations over the peak of
their dtype and least bytes over HBM bandwidth), over the pass program's
device time in the traced passes. The pass's time, not the sum of its
kernels' events, is the base: the compiler moves operands into on-chip
memory with copies that overlap other operations, so a kernel's own event
can leave out the reading of its weights."""

from bench.lib.costs import share


def read(rec):
    net = rec["trace"].get("net")
    if not net:
        return None
    return share(net["passes"] * sum(net["least_s"].values()),
                 net["pass_device_s"])
