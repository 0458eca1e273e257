"""Decode time per step, from the program's own host-clock span
(``GenerationResult.decode_s`` over the batch's decode steps)."""


def read(rec):
    c = rec["counters"]
    if not c.get("serve.decode_steps"):
        return None
    return 1e3 * c["serve.decode_s"] / c["serve.decode_steps"]
