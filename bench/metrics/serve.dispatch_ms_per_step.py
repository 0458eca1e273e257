"""Host time per decode step from the end of one token's fetch to the
next fetch: the step and its argmax enqueued (the program's
``repro.serve.decode_dispatch`` spans over the traced batch)."""

from bench.lib import program


def read(rec):
    return program.per_span_ms("repro.serve.decode_dispatch")
