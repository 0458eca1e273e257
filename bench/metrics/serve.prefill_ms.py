"""Prefill time per batch, from the program's own host-clock span
(``GenerationResult.prefill_s``, ended by ``block_until_ready``)."""


def read(rec):
    c = rec["counters"]
    if not c.get("serve.batches"):
        return None
    return 1e3 * c["serve.prefill_s"] / c["serve.batches"]
