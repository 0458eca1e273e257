"""The held experts' grouped matmul against its HBM roofline: the held
experts' weights at the compute dtype and each token's row in and out
(``costs_mla_moe.held_experts``) at 819 GB/s, over the device time of the
decode program's operations under the program's ``repro.moe.experts``
scope (the ``ragged_dot`` kernels among them) in the traced batch."""

from bench.lib.costs import share

SCOPE = "repro.moe.experts"


def read(rec):
    d = rec["trace"].get("decode")
    s = d and d.get("scopes", {}).get(SCOPE)
    if not s:
        return None
    return share(s["bytes_s"], s["device_s"])
