"""Host time per decode step spent waiting for the step's token and
copying it to the host (the program's ``repro.serve.token_fetch`` spans
over the traced batch)."""

from bench.lib import program


def read(rec):
    return program.per_span_ms("repro.serve.token_fetch")
