"""The decode step against HBM bandwidth: least bytes per step (every
weight at the compute dtype, the latent and rope cache read over the
positions filled, the logits; ``bench/lib/costs_mla_moe.py``) over
819 GB/s, over the decode program's device time in the traced batch."""

from bench.lib.costs import share


def read(rec):
    d = rec["trace"].get("decode")
    if not d or not d["steps"]:
        return None
    return share(d["bytes_s"], d["device_s"])
