"""The absorbed latent attention's share of its HBM roofline: the latent
and rope cache read over the positions filled, W_UK and W_UV, the query in
and the heads' output out (``costs_mla_moe.mla_decode``) at 819 GB/s, over
the device time of the decode program's operations under the program's
``repro.mla.decode`` scope in the traced batch."""

from bench.lib.costs import share

SCOPE = "repro.mla.decode"


def read(rec):
    d = rec["trace"].get("decode")
    s = d and d.get("scopes", {}).get(SCOPE)
    if not s:
        return None
    return share(s["bytes_s"], s["device_s"])
